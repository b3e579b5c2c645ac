"""The expert layers' grouped products' share of the device time of the
operations inside ``jit_train_step``, in percent: the forward kernel (three a
layer) and both backward products of each (the rows' gradient and the
weights'), told as ``benchmark/readers_routed.py`` ``grouped_product`` tells
them: by the kernel's name and by the ``tokens x experts a token``-row and
``[held, hidden, width]`` shapes only these products have. What XLA fuses into
a neighbour is counted with the neighbour. None without a trace or for a
configuration without a share of experts."""
from benchmark import readers_routed


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    got = readers_routed.step_runs_and_ns(run, readers_routed.grouped_product(c))
    return None if got is None else 100.0 * got[1] / got[2]
