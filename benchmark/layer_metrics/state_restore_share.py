"""Of the prompt tokens a page match offered in the window, the share a
state snapshot let the engine skip: the window's delta of
``prefix_tokens_reused`` over that of ``prefix_tokens_matched`` in the
engine's ``stats()``. A configuration with recurrent layers can skip prefill
only as far as the deepest matched radix node that still carries a snapshot;
1.0 says every admission found one at the end of its match, less says
snapshots were evicted before their session came back (or never taken) and
that much history was prefilled again. None where ``stats()`` has no such
counter (a program, or a configuration, without state snapshots) or nothing
was matched."""
from benchmark import readers

KEY = "prefix_tokens_matched"


def read(run):
    p = run["probe"]
    if p.stats_close is None or p.stats_open is None or KEY not in p.stats_close[1]:
        return None
    matched, reused = readers.counter_delta(run, KEY), readers.counter_delta(run, "prefix_tokens_reused")
    return reused / matched if matched else None
