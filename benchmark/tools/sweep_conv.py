"""Builder-only: ``benchmark/tools/sweep.py`` for a cell whose traffic kind
is ``conv_requests``: the same sweep with the system brought up by that
kind's ``ConvServed`` (its runner check, its pool of tail snapshots).

    python3 benchmark/tools/sweep_conv.py --workload <cell> --rates 12,16,20 --seconds 40
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import serving  # noqa: E402
from benchmark.kinds import conv_requests  # noqa: E402
from benchmark.tools import sweep  # noqa: E402

if __name__ == "__main__":
    serving.Served = conv_requests.ConvServed
    sweep.main()
