"""Loop-proof for the driver's multi-chip gate (round-3 VERDICT next #1).

The round-3 gate flipped red under load because the cross-process leg's
agent subprocess inherited the parent's ``JAX_PLATFORMS`` and initialized
the accelerator inside the dryrun.  The harness pins the child to CPU (a
same-host child of a chip-holding parent is CPU by rule) and budgets the
outer ``rt.get`` (180 s) above the collective round (60 s).
This test runs the FULL dryrun — dp/sp/tp/ep train steps, ring attention,
GPipe, tp serving, and the cross-process collective + device-envelope leg —
five times back to back: the flake rate the gate can tolerate is zero.
"""

import pytest

pytestmark = pytest.mark.full  # soak: the full dryrun 5x back-to-back
def test_dryrun_multichip_5x_loop():
    import __graft_entry__ as graft

    for i in range(5):
        graft.dryrun_multichip(8)
