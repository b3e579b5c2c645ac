"""The convolution mixers' share of the device time of the operations inside
``jit__prefill_chunk``, in percent: the same operations as
``conv_mixer_time_share`` tells (``benchmark/readers_conv.py``), at a chunk's
rows: the ``[chunk tokens, 3 x hidden]`` input projection, the taps over tail
and chunk where they are fused with a tail's result, the tails read and
written. The output projection is left out there as here. None without a trace
or for a configuration without conv layers."""
from benchmark import readers_conv


def read(run):
    return readers_conv.ops_share_percent(run, readers_conv.PREFILL)
