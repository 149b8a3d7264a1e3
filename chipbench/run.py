"""python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on; the
last line of stdout is the result.  See chipbench/README.md.
"""

import time

T_START = time.monotonic()  # set-up counts from here: imports included

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
