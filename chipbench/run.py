"""Run one benchmark cell once:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout.  See ``chipbench/harness.py``.
"""
import sys
from pathlib import Path

if __name__ == "__main__":
    # the checkout's root, in place of this script's own directory
    sys.path[0] = str(Path(__file__).resolve().parent.parent)
    from chipbench.harness import main
    sys.exit(main())
