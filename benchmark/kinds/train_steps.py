"""Traffic kind ``train_steps``: a closed loop of train steps on seeded
token batches, the loss read back every step.

Parameters (the traffic file): ``warm_steps`` (steps before the window,
after the compiling one), ``trace_s`` (length of the traced part of a traced
run). Shapes, batch, mesh and optimiser are the configuration's.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import numpy as np

from benchmark import serving, system, yardstick

now = serving.now


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ray_tpu.models.transformer import make_train_step

    config, p = ctx.config, ctx.traffic
    run_, model = config["run"], system.model_module(config)
    cfg = model.program_config(
        config, max_seq_len=run_["seq_len"], dtype=run_["dtype"], param_dtype=run_["param_dtype"],
        attention=run_["attention"], remat=run_["remat"], scan_layers=run_["scan_layers"])
    chips = ctx.cell["chips"]
    mesh = None
    if run_.get("mesh"):
        shape = [run_["mesh"][a] for a in run_["mesh_axes"]]
        assert math.prod(shape) == chips, "the mesh takes every chip of the cell"
        mesh = Mesh(np.array(jax.devices()[:chips]).reshape(shape), tuple(run_["mesh_axes"]))
    init_state, step = make_train_step(cfg, mesh=mesh, learning_rate=run_["learning_rate"])
    B, T, nb = run_["batch"], run_["seq_len"], run_["data_batches"]
    key = jax.random.key(ctx.seed % (2**31))
    t = now()
    if mesh is None:
        state = jax.jit(init_state)(key)
    else:
        plain_init, _ = make_train_step(cfg, learning_rate=run_["learning_rate"])
        shardings = system.train_state_shardings(cfg, mesh, jax.eval_shape(plain_init, key))
        state = jax.jit(plain_init, out_shardings=shardings)(key)
    data = jax.jit(lambda k: jax.random.randint(k, (nb, B, T), 0, cfg.vocab_size, jnp.int32))(
        jax.random.fold_in(key, 1))
    batches = [step.shard_batch(data[i]) if mesh is not None else data[i] for i in range(nb)]
    jax.block_until_ready((state, batches))
    ctx.log(f"state and {nb} token batches on the device in {now() - t:.1f} s")

    # correctness: the first step's loss against the reference on the same batch
    t = now()
    _, ref_loss = model.make_reference(config)
    want = ref_loss(state["params"], batches[0])
    ctx.log(f"reference loss {want:.5f} in {now() - t:.1f} s")
    t = now()
    state, loss = step(state, batches[0])
    got = float(jax.block_until_ready(loss))
    ctx.log(f"first step (compiles on a cold cache) in {now() - t:.1f} s, loss {got:.5f}")
    tol = run_["correctness"]["loss_rel_tol"]
    correctness = {"loss": got, "reference_loss": want, "rel_err": abs(got - want) / abs(want),
                   "rel_tol": tol}
    correctness["ok"] = math.isfinite(got) and correctness["rel_err"] < tol
    for i in range(int(p["warm_steps"])):
        state, loss = step(state, batches[(i + 1) % nb])
    float(jax.block_until_ready(loss))

    probe = ctx.probe(None, None)
    seconds, trace_s = ctx.seconds, float(p["trace_s"])
    steps, losses = [], []
    i = int(p["warm_steps"]) + 1
    t_open = now()
    window = (t_open, t_open + seconds)
    probe.window = window
    while True:
        s = now()
        if ctx.trace and not probe.tracing and not probe.traced and s - t_open >= 0.25 * seconds:
            probe.start_trace()
            s = now()
        state, loss = step(state, batches[i % nb])
        value = float(loss)  # the host read closes the step: block_until_ready and a transfer
        e = now()
        steps.append((s, e))
        losses.append(value)
        i += 1
        if probe.tracing and e - probe.trace_started >= trace_s:
            probe.stop_trace()
        if e >= window[1]:
            break
    probe.window_closed()
    del state

    tokens_per_step = B * T
    rate, n = yardstick.whole_steps_rate(steps, window, tokens_per_step)
    inside = [(s, e) for s, e in steps if s >= window[0] and e <= window[1]]
    in_window_losses = losses[: len(inside)]
    reasons = []
    bad = sum(1 for x in in_window_losses if not math.isfinite(x))
    if not correctness["ok"]:
        reasons.append(f"first step's loss disagrees with the reference: {correctness}")
    if bad:
        reasons.append(f"{bad} losses in the window are not finite")
    if in_window_losses and not in_window_losses[-1] < in_window_losses[0]:
        reasons.append(f"the window's last loss {in_window_losses[-1]} is not below its first {in_window_losses[0]}")
    if rate is None:
        reasons.append("no whole step completed inside the window")
    return {
        "window": window, "values": {"tokens_per_s": rate}, "attempted": n, "failed": bad,
        "reasons": reasons, "probe": probe, "steps": inside, "losses": in_window_losses,
        "tokens_per_step": tokens_per_step, "correctness": correctness,
        "flops_per_token": model.train_flops_per_token(config, T),
        "compared": {"first_loss_rel_err": [correctness["rel_err"], tol], "nonfinite_losses": [bad, 0]},
    }
