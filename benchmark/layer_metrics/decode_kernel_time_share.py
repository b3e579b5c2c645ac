"""The Mosaic custom-calls' share of the device time of the operations
inside ``jit__decode_k_paged``, in percent (the paged decode kernel)."""
from benchmark import readers


def read(run):
    return readers.kernel_share_percent(run, "jit__decode_k_paged")
