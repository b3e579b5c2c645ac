"""Traffic kind ``open_loop_requests``: independent requests on a schedule,
whether or not earlier ones have finished.

Parameters (the traffic file): ``rate`` (requests/s), ``prompt_tokens`` and
``output_tokens`` (distributions, see ``stratify.stratified_sizes``),
``ramp_s`` (the same process before the window: served, not scored),
``first_token_timeout_s``, and above the knee ``"backlog": "expected"``
(``serving.backlog_close``; the order of first tokens is then judged:
``serving.overtaken``).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List

import numpy as np

from benchmark import serving, stratify, system


def schedule(p: Dict[str, Any], seconds: float) -> List[Dict[str, Any]]:
    """Requests as dicts (due relative to the window's opening, prompt_len,
    max_tokens, scored), ramp first. One realisation of the stated process
    (``stratify.SCHEDULE_SEED``): the count, the lengths (stratified), which
    length comes when, and the arrival instants are the same in every run.
    ``--seed`` decides only the tokens and the weights."""
    rng = np.random.default_rng([stratify.SCHEDULE_SEED, 1])
    out: List[Dict[str, Any]] = []
    for scored, start, span in ((False, -float(p["ramp_s"]), float(p["ramp_s"])), (True, 0.0, float(seconds))):
        n = int(round(p["rate"] * span))
        if n == 0:
            continue
        prompts = stratify.shuffled(rng, stratify.stratified_sizes(p["prompt_tokens"], n))
        outputs = stratify.shuffled(rng, stratify.stratified_sizes(p["output_tokens"], n))
        arrivals = stratify.stratified_arrivals(rng, n, p["rate"], start)
        for i in range(n):
            out.append({"due": arrivals[i], "prompt_len": prompts[i],
                        "max_tokens": outputs[i], "scored": scored})
    out.sort(key=lambda r: r["due"])
    return out


def served_check_prompt_tokens(run: Dict[str, Any]) -> List[int]:
    """Prompt lengths of the served-path check, requests like the traffic's
    own: a short prompt, one of two prefill chunks, and one of three or as
    long as the logits check's longest (``run.correctness.max_prompt``),
    whichever is longer."""
    C = run["prefill_chunk_tokens"]
    return [C // 4, C + C // 3, max(2 * C + C // 2, int(run["correctness"]["max_prompt"]))]


def run(ctx) -> Dict[str, Any]:
    return serving.run_served(ctx, drive)


def drive(ctx, served: serving.Served, p: Dict[str, Any], seconds: float, seed=None) -> Dict[str, Any]:
    """One ramp and one window against a system that is already up."""
    seed = ctx.seed if seed is None else seed
    plan = schedule(p, seconds)
    rng = np.random.default_rng([seed, 2])
    vocab = served.cfg.vocab_size
    served.check_served([[rng.integers(1, vocab, size=n).tolist()] for n in served_check_prompt_tokens(served.run)])
    turns = [
        serving.Turn(r["due"], rng.integers(1, vocab, size=r["prompt_len"]).tolist(),
                     r["max_tokens"], r["scored"])
        for r in plan
    ]
    stop = threading.Event()
    threads: List[threading.Thread] = []
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
    served.correctness["window sample"] = serving.check_window_against_reference(served, turns, window, ctx.seed)
    ctx.log(f"the window's finished requests against the reference: {served.correctness['window sample']}")
    close = serving.backlog_close(p, window)
    verdict = serving.judge(turns, served.cfg.vocab_size, served.correctness, close)
    compared = {"failed_requests": [verdict["failed"], 0]}
    if close is not None:
        ctx.log(f"{verdict['waiting']} scored requests were still waiting for a first token when the window closed")
        compared["overtaken_requests"] = [verdict["overtaken"], serving.OVERTAKEN_LIMIT]
    for name, check, value, limit in (("paged_rel_err", "paged runner", "rel_err", "rel_tol"),
                                      ("served_worst_deficit_sd", "served path", "worst_deficit_sd", "near_tie_sd"),
                                      ("window_worst_deficit_sd", "window sample", "worst_deficit_sd", "near_tie_sd")):
        if value in served.correctness.get(check, {}):
            compared[name] = [served.correctness[check][value], served.correctness[check][limit]]
    return {"window": window, "turns": turns, "values": m, "probe": probe, "served": served,
            "compared": compared, "memory_peak_bytes": memory_peak, **verdict}
