"""Latent attention's share of the device time of the operations inside
``jit__prefill_chunk``, in percent: the chunk's queries over the latent rows
they can see (``latent_paged_prefill``). Told by its result, ``[1, chunk
tokens x heads, kv_lora_rank]`` (``benchmark/readers_latent.py``). None
without a trace or for a configuration without latent attention."""
from benchmark import readers, readers_latent, trace_reduce


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    kernel = readers_latent.prefill_kernel(c)
    if plane is None or kernel is None:
        return None
    share = trace_reduce.time_share(run["events"], plane, readers_latent.PREFILL, kernel)
    return None if share is None else 100.0 * share
