"""Driving the served path: ``serve.run(LLMServer)`` behind its handle, an
open-loop client on the host clock, and the correctness check of the paged
model runner against the float32 reference. Shared by the serving traffic
kinds (``open_loop_requests``, ``sessions``) and the builder's rate sweep.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark import system

now = time.perf_counter
FIRST_K = 16  # the tokens of a reply a user reads first: about a line of text


class CompileWatch:
    """When jax compiled (or fetched from its cache) a program, on the host
    clock: read from jax's own monitoring events, so it sees every thread."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.times.append(now())

    def count_in(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


class Turn:
    """One request as the client saw it."""

    __slots__ = ("due", "prompt", "max_tokens", "scored", "sent", "token_times", "tokens",
                 "error", "trace", "cancelled", "prompt_len")

    def __init__(self, due: float, prompt: List[int], max_tokens: int, scored: bool):
        self.due, self.prompt, self.max_tokens, self.scored = due, prompt, max_tokens, scored
        self.prompt_len = len(prompt)
        self.sent: Optional[float] = None
        self.token_times: List[float] = []
        self.tokens: List[int] = []
        self.error: Optional[str] = None
        self.trace = None
        self.cancelled = False


def check_paged_against_reference(cfg, params, config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Prefill, then a few decode steps, through the paged cache (the model
    runner the engine jits: ``paged_forward_with_cache`` with its decode
    kernel as the backend selects it) against the reference's full forward
    pass, in logits. A prompt longer than one chunk is prefilled as the
    engine prefills it: in successive chunks of ``prefill_chunk_tokens`` at
    running start positions through one program; the longest prompt is then
    ``max_prompt`` long. With ``max_prompt`` within one chunk the prompts,
    shapes and draws are those of PR 24."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generation import (init_paged_cache, paged_decode_step,
                                           paged_forward_with_cache)

    run, cc = config["run"], config["run"]["correctness"]
    n, maxp, k = cc["prompts"], cc["max_prompt"], cc["decode_steps"]
    C, bs = run["prefill_chunk_tokens"], run["kv_block_size"]
    rng = np.random.default_rng([seed, 7])
    lens = rng.integers(max(2, maxp // 4), maxp + 1, size=n)
    if maxp > C:
        lens[0] = maxp  # the check always holds a prompt of every chunk count up to the longest
    prompts = [rng.integers(1, cfg.vocab_size, size=int(L)) for L in lens]
    M = -(-(-(-maxp // C) * C + k) // bs)  # pages for the chunk-padded longest prompt and the decoded tokens
    cache = init_paged_cache(cfg, n * M + 1, bs)
    bt = jnp.asarray(np.arange(1, n * M + 1, dtype=np.int32).reshape(n, M))

    @jax.jit
    def prefill(params, cache, toks, bt, start, length):  # one chunk at a traced start, as the engine's ``_prefill_chunk``
        valid = (jnp.arange(C) < length)[None, :]
        logits, cache = paged_forward_with_cache(
            cfg, params, cache, bt, toks, start + jnp.arange(C)[None, :], valid=valid, use_decode_kernel=False)
        return jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0, keepdims=False), cache

    @jax.jit
    def decode(params, cache, toks, pos, bt):
        return paged_decode_step(cfg, params, cache, toks, pos, bt)

    first = []
    for i, p in enumerate(prompts):
        for start in range(0, len(p), C):
            piece = p[start : start + C]
            toks = np.zeros((1, C), np.int32)
            toks[0, : len(piece)] = piece
            lg, cache = prefill(params, cache, jnp.asarray(toks), bt[i : i + 1], jnp.int32(start), jnp.int32(len(piece)))
        first.append(lg)
    got = [jnp.stack(first)]  # [n, V] at position len - 1
    generated = []
    toks, pos = jnp.argmax(got[0], -1).astype(jnp.int32), jnp.asarray(lens, jnp.int32)
    for _ in range(k):
        generated.append(np.asarray(toks))
        lg, cache = decode(params, cache, toks, pos, bt)
        got.append(lg)
        toks, pos = jnp.argmax(lg, -1).astype(jnp.int32), pos + 1
    got = jnp.stack(got, axis=1).astype(jnp.float32)  # [n, 1 + k, V]
    del cache

    ref_logits, _ = system.model_module(config).make_reference(config)
    T = maxp + k
    want = []
    for i, p in enumerate(prompts):
        seq = np.zeros(T, np.int32)
        seq[: len(p)] = p
        seq[len(p) : len(p) + k] = [g[i] for g in generated]
        positions = jnp.arange(len(p) - 1, len(p) + k)
        want.append(ref_logits(params, jnp.asarray(seq), positions))
    want = jnp.stack(want)
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    worst = float(jnp.max(jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)))
    finite = bool(jnp.isfinite(got).all())
    return {"rel_err": err, "worst_vector_rel_err": worst, "rel_tol": cc["rel_tol"],
            "vectors": int(n * (1 + k)), "ok": finite and err < cc["rel_tol"]}


def served_token_deficits(served: "Served", ref_logits, prompt: List[int], tokens: List[int]) -> List[float]:
    """One pass of the reference over ``prompt`` and the tokens served after
    it: by how much each served token's logit lies under the reference's best
    at its position, in units of that position's logit spread (0 for the
    reference's own top choice)."""
    import jax.numpy as jnp

    k = len(tokens)
    seq = prompt + tokens[:-1]
    padded = np.zeros(-(-len(seq) // 512) * 512, np.int32)  # causal: what follows changes nothing before it
    padded[: len(seq)] = seq
    positions = np.minimum(len(prompt) - 1 + np.arange(-(-k // 64) * 64), len(seq) - 1)  # few shapes to compile
    lg = np.asarray(ref_logits(served.params, jnp.asarray(padded), jnp.asarray(positions)))[:k]
    got = lg[np.arange(k), np.asarray(tokens)]
    return ((lg.max(-1) - got) / lg.std(-1)).tolist()


def deficits_verdict(deficits: List[float], margin: float, t0: float) -> Dict[str, Any]:
    worst = max(deficits)
    return {"ok": bool(np.isfinite(worst) and worst <= margin), "tokens": len(deficits),
            "not_top1": sum(1 for d in deficits if d > 0), "worst_deficit_sd": worst,
            "near_tie_sd": margin, "seconds": now() - t0}


def check_served_against_reference(served: "Served", conversations: List[List[List[int]]]) -> Dict[str, Any]:
    """The served path itself, as the traffic uses it. A conversation is a
    list of pieces of new tokens: its j-th request is everything before it
    (prompts and replies) plus piece j, sent through the handle at
    temperature 0 for a few tokens, whatever the prefix cache holds for it.
    Every token that comes back must be the reference's top choice at its
    position, given the prompt and the tokens before it, or within
    ``near_tie_sd`` of the top in units of that position's logit spread
    (bf16 may swap near-ties; a wrong context lands some 3-5 spreads below,
    PERF.md section 4)."""
    cc = served.run["correctness"]
    k, margin = int(cc["served_tokens"]), float(cc["near_tie_sd"])
    ref_logits, _ = system.model_module(served.config).make_reference(served.config)
    t0 = now()
    deficits: List[float] = []
    for pieces in conversations:
        history: List[int] = []
        for piece in pieces:
            prompt = history + list(piece)
            turn = Turn(now(), prompt, k, False)
            served.stream(turn, threading.Event())
            if turn.error or len(turn.tokens) != k:
                return {"ok": False, "why": f"a checked request failed: {turn.error or turn.tokens}"}
            deficits.extend(served_token_deficits(served, ref_logits, prompt, turn.tokens))
            history = prompt + turn.tokens
    return deficits_verdict(deficits, margin, t0)


WINDOW_SAMPLE = 4  # finished requests of the window that are held to the reference once it has closed


def check_window_against_reference(served: "Served", turns: List[Turn], window, seed: int) -> Dict[str, Any]:
    """What the timed path itself produced, under the window's own load:
    once the window has closed, ``WINDOW_SAMPLE`` of the requests that
    finished since it opened (the longest in prompt and reply, and the rest
    drawn from the seed; the ramp's count, they were served in the window
    too) go through the reference once each, prompt and served tokens, and
    every served token is held to ``near_tie_sd`` as in
    ``check_served_against_reference``. Greedy tokens only: the traffic
    samples none."""
    margin = float(served.run["correctness"]["near_tie_sd"])
    finished = [t for t in turns if not t.error and t.tokens and len(t.tokens) == t.max_tokens
                and t.token_times[-1] >= window[0]]
    if not finished:
        return {"ok": False, "why": "no request finished in the window: nothing to hold to the reference"}
    finished.sort(key=lambda t: (-(t.prompt_len + t.max_tokens), t.due))
    rest = finished[1:]
    picks = np.random.default_rng([seed, 11]).permutation(len(rest))[: WINDOW_SAMPLE - 1]
    sample = [finished[0]] + [rest[int(i)] for i in picks]
    ref_logits, _ = system.model_module(served.config).make_reference(served.config)
    t0 = now()
    deficits: List[float] = []
    for t in sample:
        deficits.extend(served_token_deficits(served, ref_logits, t.prompt, t.tokens))
    return dict(deficits_verdict(deficits, margin, t0), requests=len(sample), finished=len(finished),
                longest=sample[0].prompt_len + sample[0].max_tokens)


class Served:
    """The system under test: weights from the seed on the device, the
    runner checked against the reference, then one replica behind
    ``serve.run(LLMServer)`` with every shape the traffic uses warmed."""

    def __init__(self, config: Dict[str, Any], seed: int, log: Callable[[str], None]):
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
        self.correctness = {"paged runner": check_paged_against_reference(self.cfg, params, config, seed)}
        log(f"paged runner against the reference in {now() - t:.1f} s: {self.correctness}")
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

    def _warm(self) -> None:
        """The prefill chunk program (first and later chunks), the decode
        program, the samplers, and the page copy of a fully cached prompt."""
        rng = np.random.default_rng(0)
        C = self.run["prefill_chunk_tokens"]
        short = rng.integers(1, self.cfg.vocab_size, size=2 * self.run["kv_block_size"]).tolist()
        long = rng.integers(1, self.cfg.vocab_size, size=C + C // 4).tolist()
        for prompt in (short, short, long):
            turn = Turn(now(), prompt, 4, False)
            self.stream(turn, threading.Event())
            if turn.error or len(turn.tokens) != 4:
                raise RuntimeError(f"warm-up request failed: {turn.error or turn.tokens}")

    def check_served(self, conversations: List[List[List[int]]]) -> None:
        """Hold the served path to the reference on ``conversations`` (the
        traffic kind makes them look like its own requests). Once a run."""
        if "served path" in self.correctness:
            return
        self.correctness["served path"] = check_served_against_reference(self, conversations)
        self.log(f"served path against the reference: {self.correctness['served path']}")

    def stats(self) -> Dict[str, Any]:
        return self.handle.stats.remote().result(timeout=60)

    def stream(self, turn: Turn, stop: threading.Event, traced: bool = False) -> None:
        """Send ``turn`` now and read its stream to the end (or to ``stop``),
        stamping each token's arrival on the host clock."""
        from ray_tpu.runtime.context import pop_request_trace, push_request_trace

        payload = {"prompt": turn.prompt, "max_tokens": turn.max_tokens, "temperature": 0.0,
                   "stream": True}
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
                if "token" in ev:
                    turn.token_times.append(now())
                    turn.tokens.append(ev["token"])
                if stop.is_set():
                    turn.cancelled = True
                    events.close()
                    break
        except BaseException as exc:  # noqa: BLE001 — a refused, shed or failed request is a result
            turn.error = f"{type(exc).__name__}: {exc}"[:3000]

    def close(self) -> None:
        self._serve.shutdown()
        self._rt.shutdown()


def run_served(ctx, drive) -> Dict[str, Any]:
    """Bring the system up, run one ramp and window of ``drive``, take it down."""
    served = Served(ctx.config, ctx.seed, ctx.log)
    try:
        return drive(ctx, served, ctx.traffic, ctx.seconds)
    finally:
        served.close()


def sleep_until(t: float) -> None:
    """Sleep to within a fraction of a millisecond of ``t`` on the host clock."""
    while True:
        left = t - now()
        if left <= 0:
            return
        time.sleep(left - 0.0005 if left > 0.002 else left / 2)


class Sampler(threading.Thread):
    """Reads the engine's ``stats()`` every ``period`` seconds."""

    def __init__(self, served: Served, period: float = 0.5):
        super().__init__(daemon=True, name="bench-stats-sampler")
        self.served, self.period, self.samples, self._stop = served, period, [], threading.Event()

    def run(self):
        while not self._stop.wait(self.period):
            try:
                self.samples.append((now(), self.served.stats()))
            except Exception:  # noqa: BLE001 — a sample lost is not a failed run
                pass

    def stop(self):
        self._stop.set()


def first_k(turn: Turn) -> int:
    return min(FIRST_K, turn.max_tokens)


def backlog_close(p: Dict[str, Any], window) -> Optional[float]:
    """The window's close if the traffic file says ``"backlog": "expected"``
    (a rate above the knee: requests still waiting for a first token when the
    window closes are what an overloaded open loop is), else None."""
    if p.get("backlog") is None:
        return None
    if p["backlog"] != "expected":
        raise ValueError(f'traffic key "backlog" is "expected" or absent, not {p["backlog"]!r}')
    return window[1]


def answered_by(t: Turn, close: Optional[float]) -> bool:
    """Whether ``t`` is judged: always, or (a backlog is expected) only if by
    ``close`` it had a first token or had been answered with an error."""
    return close is None or bool(t.error) or (bool(t.token_times) and t.token_times[0] <= close)


def wait_for_first_tokens(turns: List[Turn], timeout_s: float, close: Optional[float] = None) -> None:
    """After the window closes: wait only until every scored request (with a
    backlog expected: every one that had its first token by ``close``) has
    its first ``FIRST_K`` tokens (or has failed), at most ``timeout_s``."""
    due = [t for t in turns if t.scored and t.sent is not None and answered_by(t, close)]
    deadline = now() + timeout_s
    while now() < deadline:
        if all(len(t.token_times) >= first_k(t) or t.error for t in due):
            return
        time.sleep(0.01)


def serve_metrics(turns: List[Turn], window) -> Dict[str, Any]:
    """The serving numbers of one window, from the client's timestamps alone."""
    from benchmark import yardstick

    ttft = [1e3 * (t.token_times[0] - t.due) for t in turns if t.scored and t.token_times]
    first = [1e3 * (t.token_times[first_k(t) - 1] - t.due)
             for t in turns if t.scored and len(t.token_times) >= first_k(t)]
    gaps: List[float] = []
    for t in turns:
        gaps.extend(1e3 * g for g in yardstick.gaps_ending_in(t.token_times, window))
    late = [1e3 * (t.sent - t.due) for t in turns if t.sent is not None]
    streamed = sum(yardstick.count_in(t.token_times, window) for t in turns)
    q = yardstick.quantile
    return {
        "output_tokens_per_s": streamed / (window[1] - window[0]) if streamed else None,
        "ttft_p50_ms": q(ttft, 0.5) if ttft else None,
        "ttft_p90_ms": q(ttft, 0.9) if ttft else None,
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "first16_mean_ms": sum(first) / len(first) if first else None,
        "itl_p99_ms": q(gaps, 0.99) if gaps else None,
        "itl_mean_ms": sum(gaps) / len(gaps) if gaps else None,
        "itl_p50_ms": q(gaps, 0.5) if gaps else None,
        "loadgen_late_p99_ms": q(late, 0.99) if late else None,
    }


OVERTAKEN_LIMIT = 2  # two requests due within a millisecond may change places between the generator and the engine's queue


def overtaken(turns: List[Turn], close: float) -> int:
    """Admission keeps the order of arrival: how many requests had a first
    token by ``close`` although one that was due before them was still
    waiting for its own then (0 where every first token came in turn). An
    engine that starves the long prompts, admits the shortest first or loses
    a request leaves such requests behind it, however fast it is."""
    sent = [t for t in turns if t.sent is not None and t.due <= close and not t.error]
    waiting = [t.due for t in sent if not answered_by(t, close)]
    if not waiting:
        return 0
    return sum(1 for t in sent if answered_by(t, close) and t.due > min(waiting))


def judge(turns: List[Turn], vocab: int, correctness: Dict[str, Any],
          close: Optional[float] = None) -> Dict[str, Any]:
    """``attempted``, ``failed`` and the reasons a run is not ``correct``:
    what the system returned, never how the host treated the generator (its
    lateness is inside every time counted from ``due`` and is reported as
    ``loadgen_late_p99_ms``; PERF.md section 4). With ``close`` (see
    ``backlog_close``) the scored requests still waiting for a first token
    at the close are counted as ``waiting`` and not judged one by one; what
    is judged of them is their order: no more than ``OVERTAKEN_LIMIT``
    requests may have been answered past one that still waits."""
    everyone = [t for t in turns if t.scored]
    scored = [t for t in everyone if answered_by(t, close)]
    reasons = []
    failed = 0
    for t in scored:
        complete = len(t.tokens) == t.max_tokens
        if t.error or len(t.token_times) < first_k(t) or (not complete and not t.cancelled):
            failed += 1
    bad_tokens = sum(1 for t in turns if len(t.tokens) > t.max_tokens
                     or any(not 0 <= x < vocab for x in t.tokens))
    for name, check in correctness.items():
        if not check["ok"]:
            reasons.append(f"{name} disagrees with the reference: {check}")
    if failed:
        first = next((t.error for t in scored if t.error), f"no first {FIRST_K} tokens in time")
        reasons.append(f"{failed} scored requests failed, e.g. {first}")
    if bad_tokens:
        reasons.append(f"{bad_tokens} requests returned too many tokens or ids outside the vocabulary")
    out = {"attempted": len(scored), "failed": failed, "reasons": reasons, "waiting": len(everyone) - len(scored)}
    if close is not None:
        out["overtaken"] = overtaken(turns, close)
        if out["overtaken"] > OVERTAKEN_LIMIT:
            reasons.append(f"{out['overtaken']} requests were answered past one that was due before them and still "
                           f"waited at the close (limit {OVERTAKEN_LIMIT}): admission does not keep the order of arrival")
    return out
