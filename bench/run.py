"""Run one benchmark cell once (see ``harness/cli.py``).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import time

T_START = time.perf_counter()   # set-up is timed from process start

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
