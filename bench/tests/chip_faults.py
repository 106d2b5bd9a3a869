#!/usr/bin/env python3
"""One run of a cell on the chip with a fault of ``faults.py`` planted under
the timed path, at the cell's own size; the result line's ``check`` gives
what the comparison read:

    python3 bench/tests/chip_faults.py <fault> --workload <cell> --seed <n> \
        --seconds <s> --trace 0
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE)]

import faults  # noqa: E402
import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(sys.argv[2:], fault=faults.FAULTS[sys.argv[1]]))
