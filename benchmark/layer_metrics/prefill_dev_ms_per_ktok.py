"""Device time of ``jit__prefill_chunk`` per 1000 prompt tokens prefilled:
the traced mean time of one run, times the runs the engine counted over the
window (``prefill_chunks``), over the prompt tokens sent in the window less
those the prefix cache reused."""
from benchmark import readers


def read(run):
    runs_ms = readers.program_ms(run, "jit__prefill_chunk")
    chunks = readers.counter_delta(run, "prefill_chunks")
    reused = readers.counter_delta(run, "prefix_tokens_reused")
    if not runs_ms or chunks is None or reused is None:
        return None
    prefilled = readers.prompt_tokens_sent_in_window(run) - reused
    if prefilled <= 0:
        return None
    return (sum(runs_ms) / len(runs_ms)) * chunks / (prefilled / 1000.0)
