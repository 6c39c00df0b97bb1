"""Run one benchmark cell once, on the chip this process is started on.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are named in
BENCHMARK.json at the root of the checkout; the last line of standard
output is the result as one JSON object. Without a TPU the command fails.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
