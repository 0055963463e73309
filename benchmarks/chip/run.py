#!/usr/bin/env python3
"""Chip benchmark entry point: ``python3 benchmarks/chip/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>``, from the checkout's root.
See ``harness.py``."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
