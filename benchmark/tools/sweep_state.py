"""Builder-only: ``benchmark/tools/sweep.py`` for a cell whose traffic kind
is ``state_sessions``: the same sweep with the system brought up by that
kind's ``StateServed`` (its runner check, its pool of state snapshots).

    python3 benchmark/tools/sweep_state.py --workload <cell> --rates 1.5,2,2.5 --seconds 40
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import serving  # noqa: E402
from benchmark.kinds import state_sessions  # noqa: E402
from benchmark.tools import sweep  # noqa: E402

if __name__ == "__main__":
    serving.Served = state_sessions.StateServed
    sweep.main()
