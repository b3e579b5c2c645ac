"""``prefix_tokens_reused`` over the window (``stats()`` deltas) over the
prompt tokens the client sent in it: the share of prompt tokens that were
never prefilled."""
from benchmark import readers


def read(run):
    reused = readers.counter_delta(run, "prefix_tokens_reused")
    sent = readers.prompt_tokens_sent_in_window(run)
    return None if reused is None or not sent else reused / sent
