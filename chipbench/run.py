#!/usr/bin/env python3
"""chipbench: python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>, from the root of a checkout, on a machine
that holds the chips the cell asks for. The last line of standard
output is the result object; the lines before it are explained in
README.md. Exits non-zero, with no result line, without those chips."""

import time

T_PROCESS = time.perf_counter()     # setup_s counts from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from chipbench.harness import main
    sys.exit(main(sys.argv[1:], T_PROCESS))
