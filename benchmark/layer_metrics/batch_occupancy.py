"""Mean ``active_slots`` of the engine's ``stats()``, sampled twice a
second inside the window."""


def read(run):
    sampler = run["probe"].sampler
    if sampler is None:
        return None
    w0, w1 = run["window"]
    xs = [s["active_slots"] for t, s in sampler.samples if w0 <= t <= w1]
    return sum(xs) / len(xs) if xs else None
