"""Run one benchmark cell on the chip this process finds.

    python3 benchmarks/chip/run.py --workload r18-backlog --seed 7 \
        --seconds 10 --trace 0

Prints progress and the compared numbers on standard error, and one
JSON object as the last line of standard output.  Exits non-zero, with
no result line, where JAX finds no TPU or fewer chips than the cell
asks for.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE))
sys.path.insert(0, str(_HERE.parents[1] / "src"))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
