"""The on-chip benchmark of paddle-tpu: the yardstick later PRs are held to.

Everything a cell needs that is not the program under test lives here:
traffic generation, the plain float32 reference, the table of peaks, the
FLOP and byte counts, the reduction from traces and spans to metrics and
the comparison that decides `correct`. `BENCHMARK.json` at the root names
the cells; `run.py` runs one cell once. PERF.md says how to add a cell.
"""
