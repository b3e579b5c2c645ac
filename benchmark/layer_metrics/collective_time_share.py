"""Time in XLA's collective operations over the device's busy time, in
percent, averaged over the chips."""
from benchmark import readers


def read(run):
    shares = readers.collective_shares_percent(run)
    return None if shares is None else shares["collective"]
