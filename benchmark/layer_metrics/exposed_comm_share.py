"""Collective time during which no other operation ran on that chip, over
the traced window, in percent, averaged over the chips."""
from benchmark import readers


def read(run):
    shares = readers.collective_shares_percent(run)
    return None if shares is None else shares["exposed"]
