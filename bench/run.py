#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Needs a TPU whose ``device_kind`` is in
``bench/peaks.json``, and as many chips as the cell asks for: otherwise it
exits non-zero and prints no result. Progress goes to standard output, the
numbers compared for ``correct`` to standard error, and the last line of
standard output is the result as one JSON object.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
