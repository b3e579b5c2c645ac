"""Builder-only: ``benchmark/tools/sweep.py`` for a cell whose traffic kind
is ``block_requests``: the same sweep with the system brought up by that
kind's ``BlockServed`` (its runner check and its block events).

    python3 benchmark/tools/sweep_blocks.py --workload <cell> --rates 4,5,6 --seconds 40
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import serving  # noqa: E402
from benchmark.kinds import block_requests  # noqa: E402
from benchmark.tools import sweep  # noqa: E402

if __name__ == "__main__":
    serving.Served = block_requests.BlockServed
    sweep.main()
