"""Run one benchmark cell once on the chip this process finds:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits 2, with no result line, where JAX finds no TPU or fewer chips than
the cell asks for. See ``bench/harness.py``.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
