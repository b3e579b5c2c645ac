"""Prefill chunks a request admitted in the window cost: the window's delta
of ``prefill_chunks`` over that of ``state_restores`` + ``state_zeroed`` (a
configuration with recurrent layers counts every admission as one or the
other) in the engine's ``stats()``. 1.0 says every turn found its pages and
its snapshot and prefilled its new tokens only; a session whose wait outlasted
what the two pools keep prefills its history again, 3-5 chunks, and
``state_restore_share`` does not see it where the *pages* went (a shorter
match offers less). None where ``stats()`` has no such counter or nothing
was admitted."""
from benchmark import readers

KEYS = ("state_restores", "state_zeroed")


def read(run):
    p = run["probe"]
    if p.stats_close is None or p.stats_open is None or any(k not in p.stats_close[1] for k in KEYS):
        return None
    admitted = sum(readers.counter_delta(run, k) for k in KEYS)
    return readers.counter_delta(run, "prefill_chunks") / admitted if admitted else None
