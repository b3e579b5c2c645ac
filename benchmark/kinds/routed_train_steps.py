"""Traffic kind ``routed_train_steps``: ``train_steps``' closed loop of train
steps (seeded token batches, the loss read back every step) for a
configuration whose expert layers learn: before the window it holds the
program's **gradients** and its **first update** to the reference's, and the
routers' load rule to its own counts; in the window it reads the assignment
counts the train state carries (``state["expert_load"]``).

Parameters (the traffic file): ``warm_steps`` (steps before the window, after
the compiling one; the load rule is checked on each), ``trace_s`` (length of
the traced part of a traced run). Shapes, batch and optimiser are the
configuration's ``run`` group (``learning_rate`` the peak of a linear warm-up
over ``warmup_steps``: step ``n`` runs at ``n / warmup_steps`` of it); its
``correctness`` group holds the limits.

Before the window, on batch 0 at the timed sizes (:func:`held_before_the_window`):

1. the gradient of the program's own ``loss_and_load`` (the function the step
   differentiates) against the reference's float32 gradient taken a layer at
   a time, relative Frobenius error a leaf, the largest of each group of
   leaves beside the group's limit, **twice**: the program at float32
   activations and "highest" product precision (``f32.*``: the same
   ``loss_fn``, the same kernels; tight limits that a reference with
   bfloat16 products or parameters fails), and the program as the step runs
   it (bf16 activations over f32 parameters: limits that hold a wrong
   function). Both run on the parameters alone, before the optimizer's
   moments exist: every gradient fits then;
2. the first step's loss against the reference's (``train_steps``' check),
   and the **parameters' change** over that step against the reference's
   AdamW step (``adamw_first_step`` at the warm-up's first rate) from the
   gradient (1) has just held: ``|new - reference's new| / |reference's new -
   old|``, the worst leaf. A state left unchanged, a leaf skipped or
   parameters kept in bfloat16 read 1;
3. on the first and every warm step, each expert layer's counts sum to tokens
   x k, and each bias moved by exactly ``+-gamma`` an expert in the direction
   the step's counts say (``benchmark/models/<model>.py`` ``bias_step``).

Routing near-ties: where the k-th and (k+1)-th ``score + bias`` of a token
lie closer than program and reference compute them apart, they choose
different experts for it. At bf16 activations that is ~3% of an expert's rows,
and the groups' limits hold room for it; at float32 it is a token in some
runs, which moves a leaf by ~1 / sqrt(its rows). ``f32.routing_swaps`` counts
them (half the summed difference of program's and reference's counts an
expert layer) beside a limit a bfloat16 product exceeds a hundredfold, and
the float32 limits hold room for that many. The window's counts are not
compared with the reference's (the program's own sum and its own rule are).

``variant`` (``benchmark/tools/routed_train_control.py`` only) puts a control
in the program's place, so that it is this file's comparison that has to
refuse it: ``reference:<control>`` (the reference made another function
stands where the float32 program's gradients do), ``bf16_state`` (the
parameters kept in bfloat16), ``unchanged_state`` (the first step's update
thrown away).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import numpy as np

from benchmark import serving, system, yardstick

now = serving.now

# the leaves a limit is set for, by the program's names; every other leaf is "other"
GROUPS = {"router": "router", "lat_wq": "lat_wq", "lat_wkva": "lat_wkva", "we1": "we1", "we2": "we2", "we3": "we3",
          "ws1": "shared", "ws2": "shared", "ws3": "shared", "head": "head"}


def learning_rate(run: Dict[str, Any]):
    """What ``make_train_step`` takes: the run's rate, or its linear warm-up
    (step ``n``, from 1, at ``n / warmup_steps`` of it) as an optax schedule."""
    import optax

    peak, warmup = float(run["learning_rate"]), int(run.get("warmup_steps", 0))
    if not warmup:
        return peak
    return optax.linear_schedule(peak / warmup, peak, warmup - 1)


def program_configs(config: Dict[str, Any], model, variant: Optional[str] = None):
    """(the run's program config, the same at float32 activations under full
    recomputation: the tight comparison's)."""
    run = config["run"]
    kw = dict(max_seq_len=run["seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"],
              attention=run["attention"], remat=run["remat"], scan_layers=run["scan_layers"])
    if variant == "bf16_state":
        kw["param_dtype"] = "bfloat16"
    return model.program_config(config, **kw), model.program_config(config, **{**kw, "dtype": "float32", "remat": True})


def program_gradients(cfg, params, tokens, highest: bool = False):
    """(loss, the expert layers' counts, gradients) of the function the train
    step differentiates."""
    import jax

    from ray_tpu.models.transformer import loss_and_load

    # (the tokens an argument: closed over they would be a constant of the program, and every seed a cache miss)
    fn = jax.jit(jax.value_and_grad(lambda p, t: loss_and_load(cfg, p, t), has_aux=True))
    with jax.default_matmul_precision("highest" if highest else "default"):
        (loss, load), grads = fn(params, tokens)
    return float(loss), np.asarray(load).astype(np.int64), jax.block_until_ready(grads)


def reference_gradients(reference, params, tokens):
    """(loss, counts, the gradients as a tree of the program's shape): a
    reference standing in a program's place."""
    import jax.numpy as jnp

    parts: Dict[Any, Any] = {}
    counts = []
    loss = reference.loss_and_grads(params, tokens, lambda s, i, leaf, g: parts.__setitem__((s, i, leaf), g), counts.append)
    grads: Dict[str, Any] = {}
    for (stack, index, leaf), g in parts.items():
        if leaf is None:
            grads[stack] = g.reshape(params[stack].shape)
        else:
            grads.setdefault(stack, {}).setdefault(leaf, {})[index] = g.reshape(params[stack][leaf].shape[1:])
    for stack, leaves in grads.items():
        if isinstance(leaves, dict):
            grads[stack] = {leaf: jnp.stack([by_index[i] for i in sorted(by_index)]) for leaf, by_index in leaves.items()}
    return loss, np.asarray(sum(counts)).astype(np.int64), grads


def gradient_errors(reference, params, tokens, programs: Dict[str, Any]):
    """(reference loss, reference counts, {program: {group: largest relative
    Frobenius error of its leaves}}) of each program's gradient tree against
    the reference's gradients on ``tokens`` [B, T]."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rel(got, want):
        want = want.reshape(got.shape).astype(jnp.float32)
        return jnp.linalg.norm((got.astype(jnp.float32) - want).ravel()) / jnp.maximum(jnp.linalg.norm(want.ravel()), 1e-30)

    errors: Dict[str, Dict[str, float]] = {name: {} for name in programs}
    counts = []

    def sink(stack, index, leaf, want):
        group = GROUPS.get(leaf or stack, "other")
        for name, grads in programs.items():
            got = grads[stack] if leaf is None else grads[stack][leaf][index]
            errors[name][group] = max(errors[name].get(group, 0.0), float(rel(got, want)))

    ref_loss = reference.loss_and_grads(params, tokens, sink, counts.append)
    return ref_loss, np.asarray(sum(counts)).astype(np.int64), errors


def swaps(counts, ref_counts) -> int:
    """Assignments that went to another expert than the reference's: half
    the summed difference of the counts (a swap takes one from an expert and
    gives one to another)."""
    return int(np.abs(np.asarray(counts) - np.asarray(ref_counts)).sum() // 2)


def parameter_change_error(model, rate: float, old, new, grads):
    """(the worst leaf's ``|new - reference's new| / |reference's new - old|``
    and its name), the reference's AdamW first step from ``grads``; the
    routers' bias, which the optimizer does not train, left out."""
    import jax
    import jax.numpy as jnp

    def one(p_old, p_new, g):
        want = model.adamw_first_step(p_old, g, rate)
        moved = jnp.linalg.norm((want - p_old.astype(jnp.float32)).ravel())
        return jnp.linalg.norm((p_new.astype(jnp.float32) - want).ravel()) / jnp.maximum(moved, 1e-30)

    def trained(tree):
        return {**tree, "layers": {k: v for k, v in tree["layers"].items() if k != "router_bias"}}

    errs = jax.jit(lambda a, b, g: jax.tree.map(one, a, b, g))(trained(old), trained(new), trained(grads))
    by_leaf = {jax.tree_util.keystr(path): float(e) for path, e in jax.tree_util.tree_leaves_with_path(errs)}
    worst = max(by_leaf, key=lambda k: by_leaf[k] if math.isfinite(by_leaf[k]) else math.inf)
    return by_leaf[worst], worst


def load_rule_faults(model, bias_before, bias_after, load, tokens_per_step: int, top_k: int, gamma: float):
    """(entries of the bias that are not where the rule puts them, layers
    whose counts do not sum to tokens x k) of one step."""
    expected = model.bias_step(bias_before, load, gamma)
    off = int(np.sum(np.abs(np.asarray(bias_after, np.float32) - expected) > 1e-7))
    return off, int(np.sum(load.sum(axis=-1) != tokens_per_step * top_k))


def verdict(limits: Dict[str, Any], read: Dict[str, Any]):
    """(``compared``: each number that decides ``correct`` beside its limit;
    the reasons it is not). ``read``: ``loss_err``, ``grad_err`` by group,
    ``f32`` (the same two, and ``swaps``), ``param_change``, ``rule_off``,
    ``sum_off``."""
    def by_group(errors, tols):
        return {group: [err, tols.get(group, tols["other"])] for group, err in sorted(errors.items())}

    compared = {"first_loss_rel_err": [read["loss_err"], limits["loss_rel_tol"]],
                "f32.loss_rel_err": [read["f32"]["loss_err"], limits["f32"]["loss_rel_tol"]],
                "f32.routing_swaps": [read["f32"]["swaps"], limits["f32"]["routing_swaps"]]}
    compared.update({f"f32.grad_rel_err.{g}": v for g, v in by_group(read["f32"]["grad_err"], limits["f32"]["grad_rel_tol"]).items()})
    compared.update({f"grad_rel_err.{g}": v for g, v in by_group(read["grad_err"], limits["grad_rel_tol"]).items()})
    compared["param_change_rel_err"] = [read["param_change"], limits["param_change_rel_tol"]]
    reasons = [f"{k} is {v} against a limit of {lim}" for k, (v, lim) in compared.items()
               if not (math.isfinite(v) and v < lim)]
    compared.update({"bias_off_the_rule": [read["rule_off"], 0], "layers_whose_counts_do_not_sum": [read["sum_off"], 0]})
    if read["rule_off"] or read["sum_off"]:
        reasons.append(f"the load rule: {read['rule_off']} bias entries are not where their step's counts put them, "
                       f"{read['sum_off']} layers' counts do not sum to tokens x k")
    return compared, reasons


def held_before_the_window(config: Dict[str, Any], traffic: Dict[str, Any], seed: int, log, variant: Optional[str] = None):
    """Everything this kind holds the program to before its window opens.
    Returns the train state after the warm steps and what the window needs,
    with ``compared`` and ``reasons``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import init_params, make_train_step

    run_, model = config["run"], system.model_module(config)
    cfg, cfg32 = program_configs(config, model, variant)
    gamma = float(run_["router_bias_rate"])
    init_state, step = make_train_step(cfg, learning_rate=learning_rate(run_))
    B, T, nb = run_["batch"], run_["seq_len"], run_["data_batches"]
    tokens_per_step, top_k = B * T, config["num_experts_per_tok"]
    key = jax.random.key(seed % (2**31))
    data = jax.jit(lambda k: jax.random.randint(k, (nb, B, T), 0, cfg.vocab_size, jnp.int32))(jax.random.fold_in(key, 1))
    batches = [data[i] for i in range(nb)]
    make_params = jax.jit(lambda k: init_params(cfg, k))   # the state's own: the same key through the same initialiser
    reference = model.make_reference(config)

    # 1. gradients, on the parameters alone
    t = now()
    params = make_params(key)
    if variant and variant.startswith("reference:"):
        loss32, load32, grads32 = reference_gradients(model.make_reference(config, variant.split(":", 1)[1]), params, batches[0])
    else:
        loss32, load32, grads32 = program_gradients(cfg32, params, batches[0], highest=True)
    log(f"gradients at float32 in {now() - t:.1f} s")
    t = now()
    _, _, grads = program_gradients(cfg, params, batches[0])
    log(f"the step's gradients in {now() - t:.1f} s")
    t = now()
    ref_loss, ref_load, err = gradient_errors(reference, params, batches[0], {"f32": grads32, "bf16": grads})
    read: Dict[str, Any] = {"grad_err": err["bf16"], "f32": {
        "grad_err": err["f32"], "loss_err": abs(loss32 - ref_loss) / abs(ref_loss), "swaps": swaps(load32, ref_load)}}
    del grads32, params
    grads = jax.device_get(grads)   # to the host: the state and the step's temporaries fill the chip
    log(f"the reference's gradients and the comparison in {now() - t:.1f} s: {read}; reference loss {ref_loss:.5f}")

    t = now()
    state = jax.jit(init_state)(key)
    jax.block_until_ready(state)
    log(f"train state on the device in {now() - t:.1f} s")

    def bias_and_load(state):
        return (np.asarray(state["params"]["layers"]["router_bias"], np.float32),
                np.asarray(state["expert_load"]).astype(np.int64))

    # 2. the first step's loss and update; 3. the load rule on it and on every warm step
    rule_off = sum_off = 0
    bias, load = bias_and_load(state)
    t = now()
    state, loss = step(state, batches[0])
    got = float(jax.block_until_ready(loss))
    log(f"first step (compiles on a cold cache) in {now() - t:.1f} s, loss {got:.5f}")
    read["loss_err"] = abs(got - ref_loss) / abs(ref_loss)
    t = now()
    old = make_params(key)
    new = old if variant == "unchanged_state" else state["params"]
    read["param_change"], worst = parameter_change_error(model, model.warmup_rate(run_, 1), old, new, grads)
    del old, new, grads
    log(f"the first update against the reference's AdamW step in {now() - t:.1f} s: {read['param_change']} at {worst}")
    for i in range(int(traffic["warm_steps"]) + 1):
        if i:
            state, loss = step(state, batches[i % nb])
        bias_after, load_after = bias_and_load(state)
        off, sums = load_rule_faults(model, bias, bias_after, load_after - load, tokens_per_step, top_k, gamma)
        rule_off, sum_off, bias, load = rule_off + off, sum_off + sums, bias_after, load_after
    float(jax.block_until_ready(loss))
    read.update(rule_off=rule_off, sum_off=sum_off)
    compared, reasons = verdict(run_["correctness"], read)
    return {"state": state, "step": step, "batches": batches, "load": load, "model": model,
            "compared": compared, "reasons": reasons}


def run(ctx) -> Dict[str, Any]:
    config, p = ctx.config, ctx.traffic
    run_ = config["run"]
    held = held_before_the_window(config, p, ctx.seed, ctx.log)
    state, step, batches, model = held["state"], held["step"], held["batches"], held["model"]
    compared, reasons = held["compared"], held["reasons"]
    nb, tokens_per_step = len(batches), run_["batch"] * run_["seq_len"]

    def load_of(state):
        return np.asarray(state["expert_load"]).astype(np.int64)

    probe = ctx.probe(None, None)
    seconds, trace_s = ctx.seconds, float(p["trace_s"])
    steps, losses = [], []
    i = int(p["warm_steps"]) + 1
    load_open = held["load"]
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
    load_close = load_of(state)
    del state, held

    rate, n = yardstick.whole_steps_rate(steps, window, tokens_per_step)
    inside = [(s, e) for s, e in steps if s >= window[0] and e <= window[1]]
    in_window_losses = losses[: len(inside)]
    bad = sum(1 for x in in_window_losses if not math.isfinite(x))
    compared["nonfinite_losses"] = [bad, 0]
    if bad:
        reasons.append(f"{bad} losses in the window are not finite")
    if in_window_losses and not in_window_losses[-1] < in_window_losses[0]:
        reasons.append(f"the window's last loss {in_window_losses[-1]} is not below its first {in_window_losses[0]}")
    if rate is None:
        reasons.append("no whole step completed inside the window")
    return {
        "window": window, "values": {"tokens_per_s": rate}, "attempted": n, "failed": bad,
        "reasons": reasons, "probe": probe, "steps": inside, "losses": in_window_losses,
        "tokens_per_step": tokens_per_step, "flops_per_token": model.train_flops_per_token(config, run_["seq_len"]),
        # the window's increment of the state's counter, and the steps it covers (the last may end past the window)
        "expert_load_window": (load_close - load_open), "expert_load_steps": len(steps),
        "compared": compared,
    }
