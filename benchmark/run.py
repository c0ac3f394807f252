#!/usr/bin/env python3
"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>: one run of one cell of BENCHMARK.json on the machine it is
started on. See benchmark/harness/runner.py."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
