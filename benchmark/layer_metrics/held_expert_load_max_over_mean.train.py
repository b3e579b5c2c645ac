"""The busiest held expert's assignments over the held experts' mean, over
the window and all expert layers' held ranges apart (the largest layer's
ratio): from the window's increment of the train state's ``expert_load``
(uint32[expert layers, routed experts], read at the window's ends). What the
grouped products' tallest group is, and whether the routers' bias rule levels
it. None without the counter or a share of experts."""


def read(run):
    c = getattr(run.get("ctx"), "config", None) or {}
    load = run.get("expert_load_window")
    if load is None or not c.get("experts_held"):
        return None
    lo, hi = c["experts_held"]
    held = load[:, lo:hi].astype(float)
    means = held.mean(axis=1)
    if not (means > 0).all():
        return None
    return float((held.max(axis=1) / means).max())
