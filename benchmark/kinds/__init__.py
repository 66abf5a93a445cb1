"""One driver per traffic kind, found by the traffic file's `kind`."""
