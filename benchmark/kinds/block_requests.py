"""Traffic kind ``block_requests``: ``open_loop_requests``' schedule against a
configuration that generates by diffusion over blocks (``block_length`` > 1:
a decode step carries a block of positions a sequence and a stream delivers
a committed block a time).

The schedule, the client's records, the metrics, the judging of failures and
of the backlog are ``open_loop_requests``' and ``serving``'s own. What differs
is what ``correct`` compares, since ``serving.served_token_deficits`` is
autoregressive by construction (token ``i + 1`` from the logits at ``i``).
Three comparisons against the plain reference
(``benchmark/models/<model>.py``), each number beside its limit in
``compared``:

1. *logits* (:func:`check_blocks_against_reference`): seeded prompts are
   prefilled in chunks and then ``blocks`` blocks are denoised and committed
   through the program's own block step with the kernels the engine uses;
   every denoising step's ``[Bk, V]`` logits are held to the reference's
   forward of everything committed plus the block as it went in: relative
   Frobenius error as stated (``rel_tol``), with the reference following the
   program's expert selections (``rel_tol_same_routing``: the arithmetic
   alone), and the largest margin by which a selection of the program's lies
   under the reference's own k-th score (``swap_margin_sd``: every swap a
   near-tie);
2. *served tokens, before the window* (:func:`check_served_blocks`): requests
   like the traffic's own stream ``served_tokens`` tokens, each with the
   denoising step at which it was unmasked; the reference replays block and
   step, and every token unmasked at a step must be its top choice at that
   position within ``near_tie_sd`` logit spreads, and the positions chosen
   at a step must be more confident than the best masked position left
   unchosen: the mean over the reply of (best unchosen - chosen), in units of
   log probability, at most ``confidence_margin``, which is negative
   (unmasking left to right reads a positive mean and fails it; the worst
   single gap is reported and judged by nothing: with seeded random weights
   the positions' confidences lie within the bf16 program's rounding of each
   other, so a single gap of the sound program and of the wrong one overlap);
3. *what the window served*: ``serving.WINDOW_SAMPLE`` finished requests held
   to (2) once the window has closed, ``window_blocks`` blocks of each.

Parameters (the traffic file): as ``open_loop_requests``.
"""

from __future__ import annotations

import contextlib
import gc
import threading
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import serving, system
from benchmark.kinds import open_loop_requests

now = serving.now


class BlockTurn(serving.Turn):
    """A request whose stream also said when each token was unmasked."""

    __slots__ = ("unmasked_at",)

    def __init__(self, due, prompt, max_tokens, scored):
        super().__init__(due, prompt, max_tokens, scored)
        self.unmasked_at: List[int] = []


# ---------------------------------------------------------------------------
# (1) the program's block step against the reference, in logits
# ---------------------------------------------------------------------------
class _Selections:
    """The program's expert selections, recorded in program order from its
    own router (``ray_tpu.models.transformer.route``, wrapped for the length
    of the check only)."""

    def __init__(self):
        self.calls: List[np.ndarray] = []

    @contextlib.contextmanager
    def recording(self):
        import jax

        from ray_tpu.models import transformer

        real = transformer.route

        def route(cfg, layer, x2):
            experts, weights = real(cfg, layer, x2)
            jax.debug.callback(lambda e: self.calls.append(np.asarray(e)), experts, ordered=True)
            return experts, weights

        transformer.route = route
        try:
            yield self
        finally:
            transformer.route = real

    def take(self, layers: int) -> List[np.ndarray]:
        """The ``layers`` calls of the program run that just finished."""
        import jax

        jax.effects_barrier()
        out, self.calls = self.calls[:layers], self.calls[layers:]
        if len(out) != layers or self.calls:
            raise RuntimeError(f"expected {layers} router calls of one program run, saw {len(out) + len(self.calls)}")
        return out


def _padded(seq: List[int], quantum: int = 512) -> np.ndarray:
    """``seq`` zero-padded to a multiple of ``quantum`` (a multiple of the
    block: under the block-causal mask what follows a block changes nothing
    in or before it), so that the reference compiles few shapes."""
    out = np.zeros(-(-len(seq) // quantum) * quantum, np.int32)
    out[: len(seq)] = seq
    return out


def check_blocks_against_reference(cfg, params, config: Dict[str, Any], seed: int,
                                   reference_params=None) -> Dict[str, Any]:
    """Comparison (1). ``reference_params``: a function that gives the
    weights the reference runs on once the program is done with ``params``
    (the builder's control of a program on lowered weights,
    ``benchmark/tools/block_precision_control.py``); the same by default."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generation import (init_paged_cache, open_blocks, paged_block_step,
                                           paged_forward_counted)

    run, cc = config["run"], config["run"]["correctness"]
    n, maxp, n_blocks = int(cc["prompts"]), int(cc["max_prompt"]), int(cc["blocks"])
    C, bs, Bk, S = run["prefill_chunk_tokens"], run["kv_block_size"], cfg.block, int(config["denoising_steps"])
    L, k = config["num_hidden_layers"], config["num_experts_per_tok"]
    rng = np.random.default_rng([seed, 7])
    lens = rng.integers(max(Bk + 1, maxp // 4), maxp + 1, size=n)
    lens[0] = maxp  # the longest: every chunk count up to it, and (as the file gives it) no whole number of blocks
    if n > 1:
        lens[1] -= lens[1] % Bk  # and one prompt of whole blocks: its first block opens all masked
    prompts = [rng.integers(1, cfg.vocab_size, size=int(x)).tolist() for x in lens]
    fills = [len(p) - len(p) % Bk for p in prompts]
    M = -(-(-(-maxp // C) * C + (n_blocks + 1) * Bk) // bs)
    cache = init_paged_cache(cfg, n * M + 1, bs)
    bt = jnp.asarray(np.arange(1, n * M + 1, dtype=np.int32).reshape(n, M))

    @jax.jit
    def prefill(params, cache, toks, bt, start, length):  # one chunk at a traced start, as the engine's ``_prefill_chunk``
        valid = (jnp.arange(C) < length)[None, :]
        _, cache, _ = paged_forward_counted(cfg, params, cache, bt, toks, start + jnp.arange(C)[None, :],
                                            valid=valid, with_logits=False)
        return cache

    @jax.jit
    def step(params, cache, state, pos, bt):
        logits, cache, state, done, _ = paged_block_step(cfg, params, cache, bt, state, pos)
        return logits, cache, state, done

    sel = [np.full((L, len(p) + (n_blocks + 1) * Bk, k), -1, np.int32) for p in prompts]
    records = []  # (prompt index, committed tokens, the block as it went in, its logits [Bk, V], selections [L, T, k])
    with _Selections().recording() as seen:
        for i, p in enumerate(prompts):
            for start in range(0, fills[i], C):
                piece = p[start : min(start + C, fills[i])]
                toks = np.zeros((1, C), np.int32)
                toks[0, : len(piece)] = piece
                cache = prefill(params, cache, jnp.asarray(toks), bt[i : i + 1], jnp.int32(start), jnp.int32(len(piece)))
                for j, e in enumerate(seen.take(L)):
                    sel[i][j, start : start + len(piece)] = e[: len(piece)]
        known = np.asarray([len(p) - f for p, f in zip(prompts, fills)], np.int32)
        tails = np.zeros((n, Bk), np.int32)
        for i, p in enumerate(prompts):
            tails[i, : known[i]] = p[fills[i] :]
        state = open_blocks(cfg, jnp.full((n,), S, jnp.int32), jnp.asarray(known), jnp.asarray(tails))
        pos = np.asarray(fills, np.int32)
        committed = [list(p[:f]) for p, f in zip(prompts, fills)]
        left = [n_blocks] * n
        while any(left):
            went_in = jax.device_get(state)
            logits, cache, state, done = step(params, cache, state, jnp.asarray(pos), bt)
            done = jax.device_get(done)
            chosen = seen.take(L)
            for i in range(n):
                for j in range(L):
                    sel[i][j, pos[i] : pos[i] + Bk] = chosen[j][i * Bk : (i + 1) * Bk]
                if not left[i]:
                    continue
                if done["committed"][i]:
                    committed[i] += went_in["toks"][i].tolist()
                    pos[i] += Bk
                    left[i] -= 1
                else:
                    T = len(committed[i]) + Bk
                    records.append((i, list(committed[i]), went_in["toks"][i].tolist(), logits[i].astype(jnp.float32),
                                    sel[i][:, :T].copy()))
    del cache
    if reference_params is not None:
        params = reference_params()

    ref_logits, _ = system.model_module(config).make_reference(config)
    margins: List[float] = []
    pairs = swapped = 0

    def following(chosen):
        def on_router(layer, m, w):
            nonlocal pairs, swapped
            with jax.default_matmul_precision("highest"):
                s = jax.nn.softmax(m @ w["router"], axis=-1)
            kth, own = jax.lax.top_k(s, k)
            prog = jnp.asarray(chosen[layer])
            have = prog[:, 0] >= 0
            differs = have & jnp.any(jnp.sort(own, -1) != jnp.sort(prog, -1), axis=-1)
            lowest = jnp.take_along_axis(s, jnp.maximum(prog, 0), axis=-1).min(-1)
            # on the host from here: a boolean pick on the device compiles a program a count
            have_h, differs_h, under = (np.asarray(x) for x in (have, differs, (kth[:, -1] - lowest) / s.std(-1)))
            pairs += int(have_h.sum())
            swapped += int(differs_h.sum())
            margins.extend(under[differs_h].tolist())
            return jnp.where(have[:, None], prog, own)

        return on_router

    got, want, want_same = [], [], []
    for i, ctx_toks, block, lg, chosen in records:
        seq = _padded(ctx_toks + block)
        positions = jnp.arange(len(ctx_toks), len(ctx_toks) + Bk)
        padded_sel = np.full((L, len(seq), k), -1, np.int32)
        padded_sel[:, : chosen.shape[1]] = chosen
        got.append(lg)
        want.append(ref_logits(params, jnp.asarray(seq), positions))
        want_same.append(ref_logits(params, jnp.asarray(seq), positions, on_router=following(padded_sel)))
    got, want, want_same = (jnp.stack(x) for x in (got, want, want_same))  # [steps, Bk, V]

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    err, err_same = rel(got, want), rel(got, want_same)
    worst = float(jnp.max(jnp.linalg.norm(got - want_same, axis=-1) / jnp.linalg.norm(want_same, axis=-1)))
    margin = max(margins) if margins else 0.0
    finite = bool(jnp.isfinite(got).all())
    ok = (finite and err < cc["rel_tol"] and err_same < cc["rel_tol_same_routing"] and margin < cc["swap_margin_sd"])
    return {"rel_err": err, "rel_tol": cc["rel_tol"], "rel_err_same_routing": err_same,
            "rel_tol_same_routing": cc["rel_tol_same_routing"], "worst_vector_rel_err_same_routing": worst,
            "swap_margin_max_sd": margin, "swap_margin_sd": cc["swap_margin_sd"],
            "swapped_share": swapped / max(pairs, 1), "steps": len(records), "vectors": len(records) * Bk,
            "prompt_lengths": [len(p) for p in prompts], "ok": bool(ok)}


# ---------------------------------------------------------------------------
# (2), (3) served tokens against the reference's replay of block and step
# ---------------------------------------------------------------------------
def replay_blocks(served, ref_logits, prompt: List[int], tokens: List[int], unmasked_at: List[int],
                  only: Optional[List[int]] = None) -> Dict[str, List[float]]:
    """The reference's replay of the whole blocks among ``tokens`` (those
    ``only`` lists, by index; all by default): for each denoising step ``s``
    of a block, one forward of everything served before the block plus the
    block as it stood before the step (known positions, the tokens unmasked
    before ``s``, the mask id elsewhere). ``deficits``: by how much each token
    unmasked at ``s`` lies under the reference's best at its position, in
    logit spreads. ``confidence_gaps``: for each position chosen at ``s``, by
    how much its confidence (log probability of the reference's candidate)
    lies under that of the best masked position left unchosen (negative:
    above it). ``left_to_right_gaps``: the same had the lowest masked
    positions been chosen: what the limit on their mean is held against."""
    import jax.numpy as jnp

    Bk, mask_id = served.cfg.block, served.cfg.mask_token_id
    known = len(prompt) % Bk
    seq = list(prompt[: len(prompt) - known])
    out: Dict[str, List[float]] = {"deficits": [], "confidence_gaps": [], "left_to_right_gaps": []}
    b = 0
    for first in range(-known, len(tokens), Bk):  # index into tokens of the block's first position
        idx = [i for i in range(first, first + Bk)]
        if idx[-1] >= len(tokens):
            break  # the last block was cut at max_tokens: what it held beyond was dropped, so it cannot be replayed
        final = [prompt[len(prompt) + i] if i < 0 else tokens[i] for i in idx]
        steps = [0 if i < 0 else unmasked_at[i] for i in idx]
        if only is None or b in only:
            for s in range(1, max(steps) + 1):
                block = [final[j] if steps[j] < s else mask_id for j in range(Bk)]
                lg = np.array(ref_logits(served.params, jnp.asarray(_padded(seq + block)),
                                         jnp.arange(len(seq), len(seq) + Bk)), np.float32)
                lg[:, mask_id] = -np.inf
                finite = np.where(np.isfinite(lg), lg, np.nan)
                top, spread = lg.max(-1), np.nanstd(finite, axis=-1)
                conf = -np.log(np.exp(lg - top[:, None]).sum(-1))  # log probability of the candidate
                masked = [j for j in range(Bk) if steps[j] >= s]
                chosen = [j for j in masked if steps[j] == s]
                rest = [j for j in masked if steps[j] > s]
                for j in chosen:
                    out["deficits"].append(float((top[j] - lg[j, final[j]]) / spread[j]))
                if rest:
                    best = max(conf[j] for j in rest)
                    out["confidence_gaps"].extend(float(best - conf[j]) for j in chosen)
                    ltr = masked[: len(chosen)]
                    best_ltr = max(conf[j] for j in masked if j not in ltr)
                    out["left_to_right_gaps"].extend(float(best_ltr - conf[j]) for j in ltr)
        seq += final
        b += 1
    return out


def replay_verdict(parts: List[Dict[str, List[float]]], cc: Dict[str, Any], t0: float) -> Dict[str, Any]:
    deficits = [x for p in parts for x in p["deficits"]]
    gaps = [x for p in parts for x in p["confidence_gaps"]]
    ltr = [x for p in parts for x in p["left_to_right_gaps"]]
    if not deficits or not gaps:
        return {"ok": False, "why": "no whole block to replay"}
    worst, gap = max(deficits), float(np.mean(gaps))
    return {"ok": bool(np.isfinite(worst) and worst <= cc["near_tie_sd"] and gap <= cc["confidence_margin"]),
            "tokens": len(deficits), "not_top1": sum(1 for d in deficits if d > 0), "worst_deficit_sd": worst,
            "near_tie_sd": float(cc["near_tie_sd"]), "mean_confidence_gap": gap,
            "confidence_margin": float(cc["confidence_margin"]), "gaps": len(gaps), "worst_confidence_gap": max(gaps),
            "left_to_right_mean_gap": float(np.mean(ltr)), "left_to_right_worst_gap": max(ltr), "seconds": now() - t0}


def check_served_blocks(served, prompts: List[List[int]]) -> Dict[str, Any]:
    """The served path itself, before the window: each prompt through the
    handle at temperature 0 for ``served_tokens`` tokens, whatever the prefix
    cache holds for it, and every whole block of the reply replayed."""
    cc = served.run["correctness"]
    k = int(cc["served_tokens"])
    ref_logits, _ = system.model_module(served.config).make_reference(served.config)
    t0 = now()
    parts = []
    for prompt in prompts:
        turn = BlockTurn(now(), prompt, k, False)
        served.stream(turn, threading.Event())
        if turn.error or len(turn.tokens) != k or len(turn.unmasked_at) != k:
            return {"ok": False, "why": f"a checked request failed: {turn.error or turn.tokens}"}
        parts.append(replay_blocks(served, ref_logits, prompt, turn.tokens, turn.unmasked_at))
    return replay_verdict(parts, cc, t0)


def check_window_blocks(served, turns: List[BlockTurn], window, seed: int) -> Dict[str, Any]:
    """What the timed path itself produced, under the window's own load:
    ``serving.WINDOW_SAMPLE`` of the requests that finished since it opened
    (the longest, and the rest drawn from the seed), ``window_blocks`` blocks
    of each (the first, the last whole one, the rest drawn from the seed)
    replayed as in :func:`check_served_blocks`."""
    cc = served.run["correctness"]
    finished = [t for t in turns if not t.error and t.tokens and len(t.tokens) == t.max_tokens
                and t.token_times[-1] >= window[0]]
    if not finished:
        return {"ok": False, "why": "no request finished in the window: nothing to hold to the reference"}
    finished.sort(key=lambda t: (-(t.prompt_len + t.max_tokens), t.due))
    rng = np.random.default_rng([seed, 11])
    rest = finished[1:]
    sample = [finished[0]] + [rest[int(i)] for i in rng.permutation(len(rest))[: serving.WINDOW_SAMPLE - 1]]
    ref_logits, _ = system.model_module(served.config).make_reference(served.config)
    Bk, t0 = served.cfg.block, now()
    parts = []
    for t in sample:
        whole = (len(t.tokens) + t.prompt_len % Bk) // Bk
        middle = rng.permutation(np.arange(1, max(whole - 1, 1)))[: max(int(cc["window_blocks"]) - 2, 0)]
        only = sorted({0, whole - 1, *(int(x) for x in middle)})
        parts.append(replay_blocks(served, ref_logits, t.prompt, t.tokens, t.unmasked_at, only))
    return dict(replay_verdict(parts, cc, t0), requests=len(sample), finished=len(finished),
                longest=sample[0].prompt_len + sample[0].max_tokens)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
class BlockServed(serving.Served):
    """``serving.Served`` for a diffusion configuration: the runner's check
    is (1) above, and a stream's event is a committed block."""

    def __init__(self, config: Dict[str, Any], seed: int, log):
        import jax

        import ray_tpu as rt
        from ray_tpu import serve
        from ray_tpu.serve.llm import LLMServer

        run = config["run"]
        model = system.model_module(config)
        self.config, self.run = config, run
        self.cfg = model.program_config(
            config, max_seq_len=run["max_seq_len"], dtype=run["dtype"], param_dtype=run["param_dtype"])
        t = now()
        params = jax.block_until_ready(
            system.make_params(self.cfg, seed, float(run["weights"]["embed_table_scale"])))
        self.params, self.log = params, log
        log(f"weights on the device in {now() - t:.1f} s")
        t = now()
        self.correctness = {"block runner": check_blocks_against_reference(self.cfg, params, config, seed)}
        log(f"block runner against the reference in {now() - t:.1f} s: {self.correctness}")
        t = now()
        self._rt, self._serve = rt, serve
        rt.init(num_cpus=2)
        made = (self.cfg, params)  # the replica is built on its own thread, later: bind values, not names
        self.handle = serve.run(
            serve.deployment(LLMServer).bind(
                lambda made=made: made,
                max_batch_size=run["max_batch_size"], max_seq_len=run["max_seq_len"],
                kv_block_size=run["kv_block_size"], kv_num_blocks=run["kv_num_blocks"],
                prefill_chunk_tokens=run["prefill_chunk_tokens"], decode_chunk=run["decode_chunk"],
                max_queued_requests=run.get("max_queued_requests", 1024),
            ),
            route_prefix=None,
        )
        self._warm()
        log(f"engine up and warm in {now() - t:.1f} s")

    def check_served(self, conversations) -> None:
        if "served path" in self.correctness:
            return
        self.correctness["served path"] = check_served_blocks(self, [c[0] for c in conversations])
        self.log(f"served path against the reference: {self.correctness['served path']}")

    def stream(self, turn, stop: threading.Event, traced: bool = False) -> None:
        """``Served.stream`` where an event is a committed block: each of its
        tokens is stamped with the event's arrival."""
        from ray_tpu.runtime.context import pop_request_trace, push_request_trace

        payload = {"prompt": turn.prompt, "max_tokens": turn.max_tokens, "temperature": 0.0, "stream": True}
        token = None
        try:
            if traced:
                from ray_tpu.observability.reqtrace import RequestTrace

                turn.trace = RequestTrace(route="benchmark")
                token = push_request_trace(turn.trace)
            turn.sent = now()
            try:
                response = self.handle.remote(payload)
            finally:
                if token is not None:
                    pop_request_trace(token)
            events = response.result(timeout=300)
            for ev in events:
                if "tokens" in ev:
                    t = now()
                    turn.token_times.extend([t] * len(ev["tokens"]))
                    turn.tokens.extend(ev["tokens"])
                    if hasattr(turn, "unmasked_at"):
                        turn.unmasked_at.extend(ev["unmasked_at"])
                if stop.is_set():
                    turn.cancelled = True
                    events.close()
                    break
        except BaseException as exc:  # noqa: BLE001 — a refused, shed or failed request is a result
            turn.error = f"{type(exc).__name__}: {exc}"[:3000]


def served_check_prompts(run: Dict[str, Any], block: int) -> List[int]:
    """Prompt lengths of the served-path check, requests like the traffic's
    own (``open_loop_requests.served_check_prompt_tokens``: a quarter chunk,
    1 1/3 and 2 1/2 chunks), each moved off a whole number of blocks so that
    the first block opens with known positions: 1, 2 and 3 of them."""
    out = []
    for i, n in enumerate(open_loop_requests.served_check_prompt_tokens(run)):
        want = i % (block - 1) + 1
        out.append(n - n % block + want)
    return out


def run(ctx) -> Dict[str, Any]:
    served = BlockServed(ctx.config, ctx.seed, ctx.log)
    try:
        return drive(ctx, served, ctx.traffic, ctx.seconds)
    finally:
        served.close()


def drive(ctx, served: BlockServed, p: Dict[str, Any], seconds: float, seed=None) -> Dict[str, Any]:
    """One ramp and one window against a system that is already up."""
    seed = ctx.seed if seed is None else seed
    plan = open_loop_requests.schedule(p, seconds)
    rng = np.random.default_rng([seed, 2])
    vocab = served.cfg.vocab_size
    served.check_served([[rng.integers(1, vocab, size=n).tolist()]
                         for n in served_check_prompts(served.run, served.cfg.block)])
    turns = [
        BlockTurn(r["due"], rng.integers(1, vocab, size=r["prompt_len"]).tolist(), r["max_tokens"], r["scored"])
        for r in plan
    ]
    stop = threading.Event()
    threads: List[threading.Thread] = []
    # The engine's loop lives in this process. What the set-up and the checks
    # allocated (the reference's compiled programs, traced jaxprs, arrays) is
    # moved out of the collector's sight before the ramp, as a deployment does
    # after its warm-up: a full collection over it inside the window stops
    # every thread, the engine's included, and the chip idles meanwhile
    # (PERF.md section 6, PR 40: 3 runs of 6 read 2-6% low, one with the
    # generator a second late)
    gc.collect()
    gc.freeze()
    t_open = serving.now() + float(p["ramp_s"]) + 0.05
    for t in turns:
        t.due += t_open
    window = (t_open, t_open + seconds)
    probe = ctx.probe(served, window)
    for t in turns:  # the generator: one thread, in order of due time
        serving.sleep_until(t.due)
        th = threading.Thread(target=served.stream, args=(t, stop, ctx.trace), daemon=True)
        th.start()
        threads.append(th)
    serving.sleep_until(window[1])
    probe.window_closed()
    serving.wait_for_first_tokens(turns, float(p["first_token_timeout_s"]), serving.backlog_close(p, window))
    stop.set()
    for th in threads:
        th.join(timeout=30)
    return finish(ctx, served, p, turns, window, probe)


def finish(ctx, served, p, turns, window, probe) -> Dict[str, Any]:
    m = serving.serve_metrics(turns, window)
    memory_peak = system.memory_peak_bytes(ctx.cell["chips"])  # before the reference runs beside the engine
    served.correctness["window sample"] = check_window_blocks(served, turns, window, ctx.seed)
    ctx.log(f"the window's finished requests against the reference: {served.correctness['window sample']}")
    close = serving.backlog_close(p, window)
    verdict = serving.judge(turns, served.cfg.vocab_size, served.correctness, close)
    compared = {"failed_requests": [verdict["failed"], 0]}
    if close is not None:
        ctx.log(f"{verdict['waiting']} scored requests were still waiting for a first token when the window closed")
        compared["overtaken_requests"] = [verdict["overtaken"], serving.OVERTAKEN_LIMIT]
    for name, check, value, limit in (
            ("block_rel_err", "block runner", "rel_err", "rel_tol"),
            ("block_rel_err_same_routing", "block runner", "rel_err_same_routing", "rel_tol_same_routing"),
            ("block_swap_margin_max_sd", "block runner", "swap_margin_max_sd", "swap_margin_sd"),
            ("served_worst_deficit_sd", "served path", "worst_deficit_sd", "near_tie_sd"),
            ("served_mean_confidence_gap", "served path", "mean_confidence_gap", "confidence_margin"),
            ("window_worst_deficit_sd", "window sample", "worst_deficit_sd", "near_tie_sd"),
            ("window_mean_confidence_gap", "window sample", "mean_confidence_gap", "confidence_margin")):
        if value in served.correctness.get(check, {}):
            compared[name] = [served.correctness[check][value], served.correctness[check][limit]]
    return {"window": window, "turns": turns, "values": m, "probe": probe, "served": served,
            "compared": compared, "memory_peak_bytes": memory_peak, **verdict}
