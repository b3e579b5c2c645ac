"""The Mosaic custom-calls' share (flash attention forward and backward) of
all device operation time in the traced part of the window, in percent."""
from benchmark import readers


def read(run):
    return readers.kernel_share_percent(run, None)
