"""Median device time of one run of the engine's jitted decode program
(``jit__decode_k_paged``) in the traced part of the window."""
from benchmark import readers


def read(run):
    return readers.median_or_none(readers.program_ms(run, "jit__decode_k_paged"))
