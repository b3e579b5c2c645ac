"""The latent decode kernel's share of the device time of the operations
inside ``jit__decode_k_paged``, in percent: the page walk over the one latent
pool (``latent_paged_decode``, ``ray_tpu/ops/decode_attention.py``). The trace
gives only an operation's instruction, opcode and result shape, so the kernel
is told from the other custom-calls (the state update, the grouped products)
by its result, which only it has: ``[slots, heads, kv_lora_rank]``, a latent
a head a row (``benchmark/readers_latent.py``). None without a trace or for a
configuration without latent attention."""
from benchmark import readers, readers_latent, trace_reduce


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    plane = readers.first_plane(run)
    kernel = readers_latent.decode_kernel(c)
    if plane is None or kernel is None:
        return None
    share = trace_reduce.time_share(run["events"], plane, readers_latent.DECODE, kernel)
    return None if share is None else 100.0 * share
