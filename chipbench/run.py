"""Run one benchmark cell on the chip this process finds.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

Prints one JSON object as the last line of standard output; exits
nonzero, with no result line, when JAX finds no TPU or fewer chips than
the cell asks for.
"""

import os
import sys
import time

PROCESS_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from chipbench import harness
    sys.exit(harness.main(sys.argv[1:], process_start=PROCESS_START))
