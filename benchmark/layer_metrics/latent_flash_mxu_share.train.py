"""The flash kernels of expanded latent attention against the MXU's peak, in
percent: the FLOPs causal attention needs forward and backward at keys of
``nope + rope`` and values of ``v_head_dim`` numbers a head (the
configuration's own function, ``latent_flash_flops``: the visible half of
``T x T``, the backward's recomputed scores not counted) in every layer, over
the flash custom-calls' device time a step (told by their results ``[heads,
T, key or value size]``, ``benchmark/readers_routed.py``) over the chip's
bf16 peak: the kernel's roofline share (at 8192 positions the K and V bytes
are three orders under the FLOPs' time). None without a trace or the function."""
from benchmark import readers_routed, system


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    got = readers_routed.step_runs_and_ns(run, readers_routed.flash_kernel(c))
    if got is None or not got[1]:
        return None
    count = getattr(system.model_module(c), "latent_flash_flops", None)
    if count is None:
        return None
    steps, ns, _ = got
    flops = c["num_hidden_layers"] * count(c, c["run"]["seq_len"], c["run"]["batch"])
    return 100.0 * flops / (ns / 1e9 / steps) / run["peak"]["bf16_flops"]
