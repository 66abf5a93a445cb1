"""Plain references, one file per model family, found by a configuration's
`family` key. Independent of paddle_tpu."""
