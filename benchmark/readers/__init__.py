"""One reader per source of per-layer numbers, found by a metric's `reader`."""
