"""The chunked recurrence's share of the device time of the operations
inside ``jit__prefill_chunk``, in percent: the linear layers' WY form over
chunks of 64 positions (the program calls it ``gated_delta_chunked``, a
``jax.named_scope`` of ``ray_tpu/ops/gated_delta.py``). The trace's events
carry an operation's instruction, opcode and result shape and no scope, so
the operations are told by the axes only the recurrence has
(``benchmark/readers_state.py``): a result with ``heads x 64`` adjacent (a
chunk's products and its triangular system) or the state's own ``heads x key
size x value size``. What XLA fuses into a neighbour is counted with the
neighbour. None without a trace or for a configuration without linear layers."""
from benchmark import readers_state


def read(run):
    return readers_state.state_ops_share_percent(run, "jit__prefill_chunk", chunked=True)
