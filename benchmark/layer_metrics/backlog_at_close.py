"""Requests the engine held without a first token when the window closed:
``queued`` + ``prefilling`` of its ``stats()`` at the close. Above the knee
it grows by (offered - completed) x seconds; a scheduler or batching gain
shrinks it."""


def read(run):
    close = run["probe"].stats_close
    return None if close is None else close[1]["queued"] + close[1]["prefilling"]
