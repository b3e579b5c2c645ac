"""The convolution mixers' share of the device time of the operations inside
``jit__decode_k_paged``, in percent: the operations only a conv layer's mixer
has, told by their results and by nothing else (``benchmark/readers_conv.py``:
the ``[rows, 3 x hidden]`` input projection, and whatever holds a ``[..,
slots, (conv_L_cache - 1) x hidden]`` tail), whatever implements them. What it
cannot tell apart and so leaves out: the ``[rows, hidden]`` output projection
(a quarter of a mixer's weights), which looks like every other layer's output.
None without a trace or for a configuration without conv layers."""
from benchmark import readers_conv


def read(run):
    return readers_conv.ops_share_percent(run, readers_conv.DECODE)
