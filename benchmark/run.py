"""One run of one cell of the benchmark:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses any platform but a TPU with the cell's number of chips (non-zero
exit, no result line). The last line of stdout is the result object.
"""
import time

T_PROCESS_START = time.monotonic()   # before anything heavy is imported

import os      # noqa: E402
import sys     # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
os.chdir(_ROOT)

if __name__ == "__main__":
    from benchmark.harness import main

    sys.exit(main(sys.argv[1:], T_PROCESS_START))
