"""Traffic kind ``state_sessions``: ``sessions``' agent loops against a
configuration whose linear layers keep a recurrent state a sequence beside
the pages of its full layers (``layer_types`` naming linear attention: Olmo
Hybrid). The schedule and the driving are ``sessions.schedule`` and
``sessions.drive``; the client's records, the metrics, the judging of
failures and of the backlog are ``serving``'s. What differs is how the
runner is held to the reference before the engine comes up:
``serving.check_paged_against_reference`` calls the paged forward with block
tables and no slots, so it cannot drive a cache with per-slot state, and it
never restores a state. Two comparisons against the plain reference
(``benchmark/models/<model>.py``), both through the engine's own programs'
functions with the kernels the engine uses, each number beside its limit in
``compared``:

1. *logits* (``state_rel_err``): seeded prompts up to ``max_prompt`` are
   prefilled chunk by chunk, as the engine prefills a prompt it takes a
   snapshot of (a chunk ends at the prompt's last whole page, what is left is
   a chunk of its own), then decoded ``decode_steps`` steps, all rows in one
   batch, each row's state at its slot; the logits of the prompt's last
   position and of every decode step against the reference's full forward.
2. *a follow-up turn from a restored snapshot* (``restored_rel_err``): the
   state each prompt had at its last whole page was copied into a pool of
   snapshots when the chunk ended there; the follow-up (prompt + what was
   decoded + ``follow_up_tokens`` new tokens) starts in another slot from
   that copy, shares the prompt's whole pages through its block table and
   prefills only what follows them; its last position's logits and
   ``decode_steps`` more against the reference's full forward of the whole
   history. A snapshot taken at the wrong token, a slot not restored, or a
   page shared past the snapshot reads as an error of order one in the
   logits only while the tokens since are few: with seeded weights most
   heads forget within tens of tokens, and after ``follow_up_tokens`` new
   ones the logits of a slot that was never restored read like the sound
   program's (``benchmark/tools/state_precision_control.py``, the
   ``no_restore`` control). So the follow-up's *state* is held to the
   reference's too (``restored_state_rel_err``): every linear layer's state
   of the slot after the whole history against the state of the reference's
   token-by-token recurrence, relative Frobenius error over all layers and
   heads of a sequence (the heads that keep thousands of tokens weigh most
   in it, and they are the ones a lost prefix shows in), the worst
   sequence's.
3. *served tokens*, before the window and a sample of what the window
   served: ``serving``'s own, as ``sessions`` has them (two-turn
   conversations on the cached system prompts: every second turn is a
   restore).
4. *the engine's own state* (``engine_state_rel_err``,
   ``check_engine_state``): (1) and (2) call the programs' functions in jits
   of their own, with their own copies between slots and snapshots; what the
   window drives is the engine's ``_prefill_chunk`` and ``_decode_k_paged``,
   its ``_place_state`` and ``_take_snapshots`` and the radix nodes'
   snapshots, at every slot. So once the engine is up, more two-turn
   conversations than it has slots go through the handle at once (every
   slot live, slots reused after foreign occupants, every admission a
   restore), and the state snapshot each of a few of them left at its
   reply's last whole page, read out of the engine's own pool
   (``LLMServer.state_snapshot``), is held to the state of the reference's
   token-by-token recurrence after exactly those tokens. An engine that
   skips the restore, snapshots the wrong token or hands a slot on with its
   last occupant's state reads as the ``no_restore`` control does. The same
   read-out says in which precision the engine keeps the state
   (``state_bf16_exact_share``): of the snapshot's non-zero values the share
   a bfloat16 holds exactly, ~2^-16 for a float32 state and 1 for one
   rounded to bfloat16 after every token, which no comparison against the
   reference can tell from the stated program with seeded weights (the
   configuration file has the readings).

Parameters (the traffic file): as ``sessions``.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import serving, system
from benchmark.kinds import sessions

now = serving.now


def check_state_against_reference(cfg, params, config: Dict[str, Any], seed: int,
                                  reference_params=None, restore: bool = True) -> Dict[str, Any]:
    """Comparisons (1) and (2). ``reference_params``: a function that gives
    the weights the reference runs on once the program is done with
    ``params`` (the builder's control of a program on lowered weights,
    ``benchmark/tools/state_precision_control.py``); the same by default.
    ``restore=False`` is that tool's other control: the follow-up's slot is
    left zero, as an engine that lost the snapshot and skipped prefill all
    the same would leave it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generation import (copy_sequence_state, init_paged_cache, init_sequence_state,
                                           paged_forward_counted)
    from ray_tpu.ops.gated_delta import lane_group, unpack_state

    run, cc = config["run"], config["run"]["correctness"]
    n, maxp, k, extra = int(cc["prompts"]), int(cc["max_prompt"]), int(cc["decode_steps"]), int(cc["follow_up_tokens"])
    C, bs = run["prefill_chunk_tokens"], run["kv_block_size"]
    rng = np.random.default_rng([seed, 7])
    lens = rng.integers(max(2 * bs, maxp // 4), maxp + 1, size=n)
    lens[0] = maxp  # the longest: every chunk count up to it, and (as the file gives it) a tail after its whole pages
    prompts = [rng.integers(1, cfg.vocab_size, size=int(x)).tolist() for x in lens]
    news = [rng.integers(1, cfg.vocab_size, size=extra).tolist() for _ in range(n)]
    M = -(-(maxp + 2 * k + extra + C) // bs)  # pages of a follow-up, its last chunk padded
    cache = init_paged_cache(cfg, 2 * n * M + 1, bs, slots=2 * n)
    snaps = init_sequence_state(cfg, n)
    tables = np.arange(1, 2 * n * M + 1, dtype=np.int32).reshape(2 * n, M)

    @jax.jit
    def chunk(params, cache, toks, bt, slot, start, length):  # one chunk at a traced start, as the engine's ``_prefill_chunk``
        valid = (jnp.arange(C) < length)[None, :]
        logits, cache, _ = paged_forward_counted(cfg, params, cache, bt, toks, start + jnp.arange(C)[None, :],
                                                 valid=valid, slots=slot)
        return jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0, keepdims=False), cache

    @jax.jit
    def decode(params, cache, toks, pos, bt, slots):
        logits, cache, _ = paged_forward_counted(cfg, params, cache, bt, toks[:, None], pos[:, None], slots=slots)
        return logits[:, 0], cache

    take = jax.jit(copy_sequence_state, donate_argnums=(0,))
    copy_in = jax.jit(copy_sequence_state, donate_argnums=(0,))

    def prefill(cache, seq, row, slot, start, snapshot_at=0, entry=0):
        """``seq[start:]`` in chunks; one ends at ``snapshot_at``, where the state goes to ``snaps[entry]``."""
        nonlocal snaps
        bt = jnp.asarray(tables[row : row + 1])
        lg, pos = None, start
        while pos < len(seq):
            m = min(C, len(seq) - pos)
            if pos < snapshot_at < pos + m:
                m = snapshot_at - pos
            toks = np.zeros((1, C), np.int32)
            toks[0, :m] = seq[pos : pos + m]
            lg, cache = chunk(params, cache, jnp.asarray(toks), bt, jnp.asarray([slot], jnp.int32),
                              jnp.int32(pos), jnp.int32(m))
            pos += m
            if pos == snapshot_at:
                snaps = take(snaps, cache, jnp.int32(entry), jnp.int32(slot))
        return lg, cache

    def decoded(cache, first, lens_, rows, slots):
        """``k`` greedy steps of all rows at once: (logits [n, 1 + k, V], the tokens fed)."""
        got, fed = [first], []
        toks, pos = jnp.argmax(first, -1).astype(jnp.int32), jnp.asarray(lens_, jnp.int32)
        bt = jnp.asarray(tables[rows])
        for _ in range(k):
            fed.append(np.asarray(toks))
            lg, cache = decode(params, cache, toks, pos, bt, jnp.asarray(slots, jnp.int32))
            got.append(lg)
            toks, pos = jnp.argmax(lg, -1).astype(jnp.int32), pos + 1
        return jnp.stack(got, axis=1).astype(jnp.float32), fed, cache

    whole = [len(p) // bs * bs for p in prompts]
    first = []
    for i, p in enumerate(prompts):
        lg, cache = prefill(cache, p, i, i, 0, snapshot_at=whole[i], entry=i)
        first.append(lg)
    got, fed, cache = decoded(cache, jnp.stack(first), lens, list(range(n)), list(range(n)))
    histories = [p + [int(f[i]) for f in fed] for i, p in enumerate(prompts)]

    # (2): another slot, the snapshot's copy, the prompt's whole pages shared, the rest prefilled
    follow = [h + new for h, new in zip(histories, news)]
    first = []
    for i, seq in enumerate(follow):
        tables[n + i, : whole[i] // bs] = tables[i, : whole[i] // bs]
        if restore:
            cache = copy_in(cache, snaps, jnp.int32(n + i), jnp.int32(i))
        lg, cache = prefill(cache, seq, n + i, n + i, whole[i])
        first.append(lg)
    got2, fed2, cache = decoded(cache, jnp.stack(first), [len(s) for s in follow], list(range(n, 2 * n)),
                                list(range(n, 2 * n)))
    finals = [s + [int(f[i]) for f in fed2] for i, s in enumerate(follow)]
    # the follow-ups' states as the slots hold them now: after every token of ``finals`` [n, L_lin, H, dk, dv]
    group = lane_group(cfg.linear_heads, cfg.linear_value_dim)
    held = jnp.swapaxes(unpack_state(cache["state"][:, n : 2 * n], group), 0, 1)
    del cache, snaps
    if reference_params is not None:
        params = reference_params()

    ref_logits, _ = system.model_module(config).make_reference(config)

    def wanted(seqs, lens_, with_states=False):
        out, states = [], []
        for seq, L in zip(seqs, lens_):
            padded = np.zeros(-(-len(seq) // 512) * 512, np.int32)  # causal: what follows changes nothing before it
            padded[: len(seq)] = seq
            got_ = ref_logits(params, jnp.asarray(padded), jnp.arange(L - 1, L + k),
                              **({"states_after": len(seq)} if with_states else {}))
            out.append(got_[0] if with_states else got_)
            states.append(got_[1] if with_states else None)
        return (jnp.stack(out), jnp.stack(states)) if with_states else jnp.stack(out)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    want = wanted(histories, lens)
    want2, states = wanted(finals, [len(s) for s in follow], with_states=True)
    err, err2 = rel(got, want), rel(got2, want2)
    heads = jnp.linalg.norm(held - states, axis=(-2, -1)) / jnp.linalg.norm(states, axis=(-2, -1))  # [n, L_lin, H]
    whole_ = (1, 2, 3, 4)  # a sequence's layers, heads and the state's two axes
    state_err = float(jnp.max(jnp.sqrt(jnp.sum((held - states) ** 2, axis=whole_) / jnp.sum(states ** 2, axis=whole_))))
    worst = float(jnp.max(jnp.linalg.norm(got2 - want2, axis=-1) / jnp.linalg.norm(want2, axis=-1)))
    finite = bool(jnp.isfinite(got).all() and jnp.isfinite(got2).all())
    return {"rel_err": err, "rel_tol": cc["rel_tol"], "restored_rel_err": err2, "restored_rel_tol": cc["restored_rel_tol"],
            "worst_restored_vector_rel_err": worst, "vectors": int(2 * n * (1 + k)),
            "restored_state_rel_err": state_err, "restored_state_rel_tol": cc["restored_state_rel_tol"],
            "state_worst_head_rel_err": float(heads.max()), "state_median_head_rel_err": float(jnp.median(heads)),
            "prompt_lengths": [int(x) for x in lens], "snapshots_at": whole,
            "ok": bool(finite and err < cc["rel_tol"] and err2 < cc["restored_rel_tol"]
                       and state_err < cc["restored_state_rel_tol"])}


def check_engine_state(served: "StateServed", seed: int) -> Dict[str, Any]:
    """Comparison (4): the engine's own state under its own load."""
    import jax.numpy as jnp

    cc, bs = served.run["correctness"], served.run["kv_block_size"]
    n, reply, vocab = int(cc["engine_conversations"]), int(cc["engine_reply_tokens"]), served.cfg.vocab_size
    lo, hi = cc["engine_new_tokens"]
    rng = np.random.default_rng([seed, 13])
    systems = [rng.integers(1, vocab, size=int(cc["engine_system_tokens"])).tolist() for _ in range(2)]
    pieces = [[rng.integers(1, vocab, size=int(rng.integers(lo, hi + 1))).tolist() for _ in range(2)] for _ in range(n)]
    histories: List[Optional[List[int]]] = [None] * n
    errors: List[str] = []

    def send(prompt: List[int], max_tokens: int) -> Optional[List[int]]:
        turn = serving.Turn(now(), prompt, max_tokens, False)
        served.stream(turn, threading.Event())
        if turn.error or len(turn.tokens) != max_tokens:
            errors.append(str(turn.error or turn.tokens))
            return None
        return turn.tokens

    def converse(i: int) -> None:
        history = list(systems[i % 2])
        for piece in pieces[i]:
            tokens = send(history + piece, reply)
            if tokens is None:
                return
            history += piece + tokens
        histories[i] = history

    for system_prompt in systems:  # as the traffic sends its agents' prompts: alone, so each has its pages and a snapshot
        send(system_prompt, 1)
    before = served.stats()
    threads = [threading.Thread(target=converse, args=(i,), daemon=True) for i in range(n)]
    t0 = now()
    for t in threads:
        t.start()
    peak = 0
    while any(t.is_alive() for t in threads):
        peak = max(peak, int(served.stats()["active_slots"]))
        time.sleep(0.05)
    deadline = now() + 30
    while served.stats()["active_slots"] and now() < deadline:  # the last replies' pages and snapshots are published
        time.sleep(0.05)
    after = served.stats()
    if errors or any(h is None for h in histories):
        return {"ok": False, "why": f"a checked request failed: {errors[:1]}"}
    load = {"conversations": n, "peak_active_slots": peak, "seconds": now() - t0,
            "restores": after["state_restores"] - before["state_restores"],
            "tokens_matched": after["prefix_tokens_matched"] - before["prefix_tokens_matched"],
            "tokens_reused": after["prefix_tokens_reused"] - before["prefix_tokens_reused"]}
    ref_logits, _ = system.model_module(served.config).make_reference(served.config)
    errs, exact = [], []
    k = int(cc["engine_compared"])
    for i in sorted({round(j * (n - 1) / max(1, k - 1)) for j in range(k)}):  # from the first admitted to the last, which waited for a slot
        history = histories[i]
        covered = (len(history) - 1) // bs * bs  # the reply's last token was never fed: the last whole page before it
        got = served.handle.state_snapshot.remote(history).result(timeout=120)
        if got is None or got["tokens"] != covered:
            return dict(load, ok=False, why=f"conversation {i} of {len(history)} tokens left a snapshot at "
                                            f"{got and got['tokens']}, not at {covered}")
        padded = np.zeros(-(-len(history) // 512) * 512, np.int32)
        padded[: len(history)] = history
        _, want = ref_logits(served.params, jnp.asarray(padded), jnp.asarray([0]), states_after=covered)
        held = np.asarray(got["state"], np.float32)
        errs.append(float(jnp.linalg.norm(jnp.asarray(held) - want) / jnp.linalg.norm(want)))
        exact.append(float(((held.view(np.uint32) & 0xFFFF) == 0)[held != 0].mean()))
    err, share = max(errs), max(exact)
    return dict(load, engine_state_rel_err=err, engine_state_rel_tol=cc["engine_state_rel_tol"], by_conversation=errs,
                state_bf16_exact_share=share, state_bf16_exact_max=cc["state_bf16_exact_max"],
                ok=bool(np.isfinite(err) and err < cc["engine_state_rel_tol"] and share < cc["state_bf16_exact_max"]))


class StateServed(serving.Served):
    """``serving.Served`` for a configuration with linear layers: the
    runner's check is (1) and (2) above, the engine gets its pool of state
    snapshots and, once it is up, is held to (4)."""

    def __init__(self, config: Dict[str, Any], seed: int, log, runner_check: bool = True):
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
        self.correctness = {}
        if runner_check:  # (a builder's control of the engine alone goes without: ``tools/state_precision_control.py``)
            t = now()
            self.correctness["state runner"] = check_state_against_reference(self.cfg, params, config, seed)
            log(f"state runner against the reference in {now() - t:.1f} s: {self.correctness}")
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
                state_snapshots=run["state_snapshots"],
            ),
            route_prefix=None,
        )
        self._warm()
        log(f"engine up and warm in {now() - t:.1f} s")
        t = now()
        self.correctness["engine state"] = check_engine_state(self, seed)
        log(f"the engine's own state against the reference in {now() - t:.1f} s: {self.correctness['engine state']}")

    def _warm(self) -> None:
        """``Served._warm`` (the chunk and decode programs, the samplers, a
        zeroed slot and a snapshot after a prompt of whole pages), then a
        prompt that extends one of them: a slot restored from a snapshot."""
        import threading

        super()._warm()
        rng = np.random.default_rng(0)  # ``Served._warm``'s own first prompt, and more
        short = rng.integers(1, self.cfg.vocab_size, size=2 * self.run["kv_block_size"]).tolist()
        turn = serving.Turn(now(), short + short[:5], 4, False)
        self.stream(turn, threading.Event())
        if turn.error or len(turn.tokens) != 4 or not self.stats().get("state_restores"):
            raise RuntimeError(f"warm-up of a restored slot failed: {turn.error or self.stats()}")

    def check_served(self, conversations) -> None:
        """``Served.check_served``; it is the last thing a run does before
        its ramp, so what the set-up and the checks allocated (the
        reference's compiled programs, traced jaxprs, arrays) is moved out
        of the collector's sight here, as a deployment does after its
        warm-up: a full collection over it inside the window stops every
        thread, the engine's included (``block_requests.drive``)."""
        super().check_served(conversations)
        gc.collect()
        gc.freeze()


def run(ctx) -> Dict[str, Any]:
    served = StateServed(ctx.config, ctx.seed, ctx.log)
    try:
        return drive(ctx, served, ctx.traffic, ctx.seconds)
    finally:
        served.close()


def drive(ctx, served: StateServed, p: Dict[str, Any], seconds: float, seed=None) -> Dict[str, Any]:
    """``sessions.drive``, and the runner's and the engine's numbers beside their limits."""
    out = sessions.drive(ctx, served, p, seconds, seed)
    runner = served.correctness["state runner"]
    out["compared"]["state_rel_err"] = [runner["rel_err"], runner["rel_tol"]]
    out["compared"]["restored_rel_err"] = [runner["restored_rel_err"], runner["restored_rel_tol"]]
    out["compared"]["restored_state_rel_err"] = [runner["restored_state_rel_err"], runner["restored_state_rel_tol"]]
    engine = served.correctness["engine state"]
    for name, limit in (("engine_state_rel_err", "engine_state_rel_tol"), ("state_bf16_exact_share", "state_bf16_exact_max")):
        if name in engine:
            out["compared"][name] = [engine[name], engine[limit]]
    return out
