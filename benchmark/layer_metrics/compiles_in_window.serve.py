"""Programs jax compiled (or fetched from its cache) inside the window, from
jax's own monitoring events; serving cells. Should read 0."""


def read(run):
    return run["compiles_in_window"]
