"""Traffic kind ``sessions``: agent loops. A session picks an agent (a fixed
long system prompt), and makes ``turns`` requests; each turn's prompt is the
system prompt + the session's history, generated tokens included, + new
tokens (a tool result); the next turn is due ``think_s`` after the previous
one completes. Closed inside a session, open across sessions: sessions start
on a stratified schedule whether or not earlier ones have finished, and a
turn's first-token time counts from when the turn was due.

Parameters: ``rate`` (sessions/s), ``agents``, ``system_prompt_tokens``,
``turns``, ``new_tokens``, ``output_tokens``, ``think_s`` (distributions),
``ramp_s``, ``first_token_timeout_s``.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List

import numpy as np

from benchmark import serving, stratify
from benchmark.kinds import open_loop_requests


def schedule(p: Dict[str, Any], seconds: float) -> List[Dict[str, Any]]:
    """Sessions as dicts: start (relative to the window's opening), agent,
    per-turn new and output token counts, think times, in_window. One
    realisation of the stated process (``stratify.SCHEDULE_SEED``), the
    same in every run: the number of sessions, the
    stratified sizes, which session gets which, and the start instants;
    agents go round-robin over the arrival order. ``--seed`` decides only
    the tokens and the weights."""
    rng = np.random.default_rng([stratify.SCHEDULE_SEED, 1])
    turns = int(p["turns"])
    out: List[Dict[str, Any]] = []
    for in_window, start, span in ((False, -float(p["ramp_s"]), float(p["ramp_s"])), (True, 0.0, float(seconds))):
        n = int(round(p["rate"] * span))
        if n == 0:
            continue
        new = stratify.shuffled(rng, stratify.stratified_sizes(p["new_tokens"], n * turns))
        outs = stratify.shuffled(rng, stratify.stratified_sizes(p["output_tokens"], n * turns))
        think = stratify.shuffled(
            rng, stratify.stratified_sizes({**p["think_s"], "round": False}, n * (turns - 1)))
        arrivals = stratify.stratified_arrivals(rng, n, p["rate"], start)
        for i in range(n):
            sl = slice(i * turns, (i + 1) * turns)
            out.append({"start": arrivals[i], "new_tokens": new[sl], "output_tokens": outs[sl],
                        "think_s": think[i * (turns - 1) : (i + 1) * (turns - 1)], "in_window": in_window})
    out.sort(key=lambda s: s["start"])
    for i, s in enumerate(out):
        s["agent"] = i % int(p["agents"])
    return out


def run(ctx) -> Dict[str, Any]:
    return serving.run_served(ctx, drive)


def drive(ctx, served: serving.Served, p: Dict[str, Any], seconds: float, seed=None) -> Dict[str, Any]:
    seed = ctx.seed if seed is None else seed
    plan = schedule(p, seconds)
    rng = np.random.default_rng([seed, 2])
    vocab = served.cfg.vocab_size
    systems = [rng.integers(1, vocab, size=int(p["system_prompt_tokens"])).tolist()
               for _ in range(int(p["agents"]))]
    stop = threading.Event()
    # the system prompts go into the prefix cache before the ramp: set-up that the traffic needs
    for sp in systems:
        warm = serving.Turn(serving.now(), sp, 1, False)
        served.stream(warm, stop)
        if warm.error:
            raise RuntimeError(f"warming a system prompt failed: {warm.error}")
    for s in plan:
        s["new"] = [rng.integers(1, vocab, size=n).tolist() for n in s["new_tokens"]]
    # two turns on each of two agents, as the traffic makes them: the cached system prompt + new
    # tokens, then that turn's prompt and reply (cached by now) + new tokens
    lo, hi = int(p["new_tokens"]["lo"]), int(p["new_tokens"]["hi"])
    served.check_served([[sp + rng.integers(1, vocab, size=hi).tolist(), rng.integers(1, vocab, size=lo).tolist()]
                         for sp in systems[:2]])

    t_open = serving.now() + float(p["ramp_s"]) + 0.05
    window = (t_open, t_open + seconds)
    turns: List[serving.Turn] = []
    lock = threading.Lock()

    def session(s):
        history = list(systems[s["agent"]])
        due = t_open + s["start"]
        for k in range(len(s["new"])):
            if k:
                due = serving.now() + s["think_s"][k - 1]
            if due >= window[1] or stop.is_set():
                return  # turns due after the window are not this run's
            prompt = history + s["new"][k]
            turn = serving.Turn(due, prompt, s["output_tokens"][k], window[0] <= due < window[1])
            with lock:
                turns.append(turn)
            serving.sleep_until(due)
            served.stream(turn, stop, ctx.trace)
            if turn.error or turn.cancelled or len(turn.tokens) != turn.max_tokens:
                return
            history = prompt + turn.tokens

    probe = ctx.probe(served, window)
    threads: List[threading.Thread] = []
    for s in plan:  # the generator: one thread starts each session when it is due
        serving.sleep_until(t_open + s["start"])
        th = threading.Thread(target=session, args=(s,), daemon=True)
        th.start()
        threads.append(th)
    serving.sleep_until(window[1])
    probe.window_closed()
    with lock:
        snapshot = list(turns)
    serving.wait_for_first_tokens(snapshot, float(p["first_token_timeout_s"]), serving.backlog_close(p, window))
    stop.set()
    for th in threads:
        th.join(timeout=30)
    return open_loop_requests.finish(ctx, served, p, turns, window, probe)
