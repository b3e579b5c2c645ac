"""Traffic kind ``conv_requests``: ``open_loop_requests``' independent
requests against a configuration whose conv layers (a gated short convolution
as the whole mixer: ``layer_types`` naming ``conv``, LFM2) keep a convolution
tail a sequence beside the pages of its full layers, and no recurrent matrix.
The schedule, the driving, the client's records, the metrics and the judging
of failures and of the backlog are ``open_loop_requests.drive`` / ``finish``
and ``serving``'s, by import. What differs is how the runner is held to the
reference before the engine comes up: ``serving.check_paged_against_reference``
calls the paged forward with block tables and no slots, so it cannot drive a
cache with per-slot state, it never cuts a chunk inside a page and never
restores a tail. Here, through the engine's own programs' functions with the
kernels the engine uses and at the engine's shapes (a cache of
``max_batch_size`` slots, a decode step of all ``max_batch_size`` rows of
which the check's few are live and the others idle on the garbage page as the
engine's are, a row's slot its row, the sequences' slots and their snapshot
entries spread over the ranges), against ``benchmark/models/<model>.py``, the
plain reference walked layer by layer, each number beside its limit in
``compared``.

**The reference follows the program's routing.** A routed model's logits are
not continuous in its weights' precision: where a token's k-th and (k+1)-th
expert lie closer than the activations' rounding, program and reference choose
differently, one of the token's k experts is another, and everything behind
it moves by tenths (``benchmark/tools/conv_precision_control.py``'s
``stated_unmatched`` reads what that costs: PERF.md). So the program hands
out the experts it ran each token through (``paged_forward_counted(...,
routes=True)``) and the reference takes them (``make_reference``'s
``on_router``): what is compared is then arithmetic alone, and a limit can
stand between the stated precision and the next lower one. The router itself
is held in (5), and ``near_tie_share`` is reported beside the limits: of the
(token, expert layer) pairs, those the reference would have routed otherwise
on the input it had.

1. *logits* (``conv_rel_err``): seeded prompts up to ``max_prompt`` are
   prefilled chunk by chunk (the first chunk ends **inside a page**, two
   one-token chunks follow it, one chunk ends at the prompt's last whole page,
   where the tails go to a pool of snapshots, what is left is a chunk of its
   own), then decoded ``decode_steps`` steps, all rows in one batch, each
   row's tails at its slot: the logits of the prompt's last position and of
   every decode step against the reference's full forward.
2. *a follow-up from restored tails* (``restored_rel_err``): the follow-up
   (prompt + what was decoded + ``follow_up_tokens`` new tokens) starts in
   another slot from the snapshot's copy, shares the prompt's whole pages and
   prefills what follows them, two one-token chunks first.
3. *right behind a boundary* (``boundary_rel_err``): the logits of the two
   one-token chunks behind the cut inside a page (1) and behind the restore
   (2), the worse of the two groups. A tail lost, zeroed or taken from another
   slot changes two of a convolution's three taps at exactly those positions,
   in every conv layer, and fades within ``conv_L_cache - 1`` tokens a layer:
   the logits further on cannot see it, these can.
4. *the tails themselves*: every slot's tails after its whole history against
   the reference's last ``conv_L_cache - 1`` inputs a conv layer, relative
   Frobenius error, the worst sequence: over every conv layer
   (``tails_rel_err``: the first sequences' slots stand idle while the
   follow-ups prefill and decode, so a step that moves an idle row's tail
   shows here and nowhere in the logits), and over the conv layers no expert
   layer lies before (``lead_tails_rel_err``). The slots no sequence was given
   stay zero to the bit (``unnamed_slots_max_abs``).
5. *the router's precision* (``router_swap_share``): the program's own router
   function on the reference's float32 input of the first expert layer,
   against the reference's selection there: the share of tokens whose chosen
   set differs. Scores in float32 at full matmul precision choose the same
   sets; in bfloat16 near-ties swap.
6. *the expert layers alone* (``expert_rel_err``): every expert layer's
   branch (the program's ``moe_ffn_dropless`` reading the stack where it lies,
   the grouped products' kernel) on one seeded input of a decode step's rows,
   ``max_batch_size`` of them, so ``k / E`` of them an expert as in the
   window's steps, against the reference's branch at the same selection: one
   layer's arithmetic and nothing before it, where the experts' precision
   shows that the logits' own noise (bf16 activations over all the layers)
   covers.
7. *served tokens*, before the window and a sample of what the window served:
   ``serving``'s own, as ``open_loop_requests`` has them. The served engine
   hands out no selections, so the reference routes for itself there and the
   limit ``near_tie_sd`` stands where near-ties put it (the configuration
   file's ``correctness.why``).

Parameters (the traffic file): as ``open_loop_requests``.
"""

from __future__ import annotations

import gc
import threading
from typing import Any, Dict

import numpy as np

from benchmark import serving, system
from benchmark.kinds import open_loop_requests

now = serving.now
schedule = open_loop_requests.schedule


def make_params(cfg, config: Dict[str, Any], seed: int):
    """The weights from the seed (``system.make_params``: the program's own
    initialiser, the tied table scaled by ``weights.embed_table_scale``), with
    the query and key norms' gains at ``weights.qk_norm_gain`` (default 1, the
    initialiser's): the configuration file says why. Exact in the type served."""
    import jax

    weights = config["run"]["weights"]
    params = system.make_params(cfg, seed, float(weights["embed_table_scale"]))
    gain = float(weights.get("qk_norm_gain", 1.0))
    if gain == 1.0:
        return params

    def scaled(path, a):
        return (a * gain).astype(a.dtype) if getattr(path[-1], "key", None) in ("q_norm", "k_norm") else a

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(scaled, p), donate_argnums=0)(params)


def check_conv_against_reference(cfg, params, config: Dict[str, Any], seed: int, reference_params=None,
                                 fault: str = None, matched: bool = True) -> Dict[str, Any]:
    """Comparisons (1) to (6). ``reference_params``: a function that gives the
    weights the reference runs on once the program is done with ``params``
    (the builder's control of a program on lowered weights,
    ``benchmark/tools/conv_precision_control.py``); the same by default.
    ``fault``: that tool's other controls: ``"zeroed_tail"`` zeroes the slot's
    tails at the cut inside a page, ``"wrong_slot"`` restores every follow-up
    from another sequence's snapshot, ``"idle_moves"`` loses the decode step's
    mask of live rows while the follow-ups decode (the first sequences' slots
    are idle then). ``matched=False``: the reference routes for itself (that
    tool's reading of what the near-ties alone cost the logits)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer
    from ray_tpu.models.generation import (copy_sequence_state, init_paged_cache, init_sequence_state,
                                           paged_forward_counted, zero_sequence_state)

    run, cc = config["run"], config["run"]["correctness"]
    n, maxp, k, extra = int(cc["prompts"]), int(cc["max_prompt"]), int(cc["decode_steps"]), int(cc["follow_up_tokens"])
    C, bs, B = run["prefill_chunk_tokens"], run["kv_block_size"], run["max_batch_size"]
    if B < 2 * n:
        raise ValueError(f"correctness.prompts {n}: each takes two of the batch's {B} slots")
    rng = np.random.default_rng([seed, 7])
    lens = rng.integers(max(4 * bs, maxp // 4), maxp + 1, size=n)
    lens[0] = maxp  # the longest: every chunk count up to it, and (as the file gives it) a tail after its whole pages
    prompts = [rng.integers(1, cfg.vocab_size, size=int(x)).tolist() for x in lens]
    news = [rng.integers(1, cfg.vocab_size, size=extra).tolist() for _ in range(n)]
    cuts = [min(int(x) // 3 // bs * bs, C - bs) + 5 for x in lens]  # inside a page: 5 tokens past its first
    M = -(-(maxp + 2 * k + extra + C) // bs)  # pages of a follow-up, its last chunk padded
    # the engine's shapes: a slot a row of the batch, the sequences' slots and snapshots spread over their ranges
    at = np.linspace(0, B - 1, 2 * n).astype(int)
    slot1, slot2 = [int(x) for x in at[0::2]], [int(x) for x in at[1::2]]
    entries = [int(x) for x in np.linspace(0, run["state_snapshots"] - 1, n).astype(int)]
    cache = init_paged_cache(cfg, 2 * n * M + 1, bs, slots=B)
    snaps = init_sequence_state(cfg, run["state_snapshots"])
    tables = np.zeros((B, M), np.int32)  # a row no sequence holds: the garbage page, as the engine's idle rows
    tables[slot1 + slot2] = np.arange(1, 2 * n * M + 1, dtype=np.int32).reshape(2 * n, M)

    @jax.jit
    def chunk(params, cache, toks, bt, slot, start, length):  # one chunk at a traced start, as the engine's ``_prefill_chunk``
        valid = (jnp.arange(C) < length)[None, :]
        logits, cache, moe = paged_forward_counted(cfg, params, cache, bt, toks, start + jnp.arange(C)[None, :],
                                                   valid=valid, slots=slot, routes=True)
        return jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0, keepdims=False), cache, moe["routes"]

    @jax.jit
    def decode(params, cache, toks, pos, bt, live):  # all ``B`` rows, the idle ones masked, as the engine's ``_decode_k_paged``
        logits, cache, moe = paged_forward_counted(cfg, params, cache, bt, toks[:, None], pos[:, None], valid=live[:, None],
                                                   slots=jnp.arange(B, dtype=jnp.int32), routes=True)
        return logits[:, 0], cache, moe["routes"]

    take = jax.jit(copy_sequence_state, donate_argnums=(0,))
    copy_in = jax.jit(copy_sequence_state, donate_argnums=(0,))
    zero = jax.jit(zero_sequence_state, donate_argnums=(0,))
    chosen = {}  # slot -> {position: int32[expert layers, k]}: what the program ran each token through

    def prefill(cache, seq, slot, start, cut, snapshot_at=0, entry=0, zero_at_cut=False):
        """``seq[start:]`` in chunks: one ends at ``cut``, two of one token
        follow it, one ends at ``snapshot_at`` (the tails go to
        ``snaps[entry]``). Returns (the last chunk's logits, the two one-token
        chunks' logits, the cache)."""
        nonlocal snaps
        bt = jnp.asarray(tables[slot : slot + 1])
        ends = sorted({e for e in (cut, cut + 1, cut + 2, snapshot_at) if start < e < len(seq)})
        lg, behind, pos = None, [], start
        while pos < len(seq):
            m = min([C, len(seq) - pos] + [e - pos for e in ends if e > pos])
            toks = np.zeros((1, C), np.int32)
            toks[0, :m] = seq[pos : pos + m]
            lg, cache, routes = chunk(params, cache, jnp.asarray(toks), bt, jnp.asarray([slot], jnp.int32),
                                      jnp.int32(pos), jnp.int32(m))
            routes = np.asarray(routes)
            chosen.setdefault(slot, {}).update({pos + j: routes[:, j] for j in range(m)})
            if pos in (cut, cut + 1) and m == 1:
                behind.append(lg)
            pos += m
            if pos == cut and zero_at_cut:
                cache = zero(cache, jnp.int32(slot))
            if pos == snapshot_at:
                snaps = take(snaps, cache, jnp.int32(entry), jnp.int32(slot))
        return lg, jnp.stack(behind), cache

    def decoded(cache, first, lens_, slots, all_live=False):
        """``k`` greedy steps of the rows at ``slots`` in one batch of ``B``:
        (logits [n, 1 + k, V], the tokens fed)."""
        got, fed = [first], []
        rows = jnp.asarray(slots)
        bt = np.zeros_like(tables)
        bt[slots] = tables[slots]
        live = jnp.ones((B,), bool) if all_live else jnp.asarray(bt[:, 0] > 0)
        toks = jnp.zeros((B,), jnp.int32).at[rows].set(jnp.argmax(first, -1).astype(jnp.int32))
        pos = np.zeros((B,), np.int32)
        pos[slots] = lens_
        for _ in range(k):
            fed.append(np.asarray(toks[rows]))
            lg, cache, routes = decode(params, cache, toks, jnp.asarray(pos), jnp.asarray(bt), live)
            routes = np.asarray(routes)
            for slot in slots:
                chosen[slot][int(pos[slot])] = routes[:, slot]
            got.append(lg[rows])
            toks = jnp.argmax(lg, -1).astype(jnp.int32)
            pos[slots] += 1
        return jnp.stack(got, axis=1).astype(jnp.float32), fed, cache

    whole = [len(p) // bs * bs for p in prompts]
    first, behind = [], []
    for i, p in enumerate(prompts):
        lg, b, cache = prefill(cache, p, slot1[i], 0, cuts[i], snapshot_at=whole[i], entry=entries[i],
                               zero_at_cut=fault == "zeroed_tail")
        first.append(lg)
        behind.append(b)
    got, fed, cache = decoded(cache, jnp.stack(first), lens, slot1)
    histories = [p + [int(f[i]) for f in fed] for i, p in enumerate(prompts)]

    # (2): another slot, the snapshot's copy, the prompt's whole pages shared, the rest prefilled
    follow = [h + new for h, new in zip(histories, news)]
    first, behind2 = [], []
    for i, seq in enumerate(follow):
        tables[slot2[i], : whole[i] // bs] = tables[slot1[i], : whole[i] // bs]
        chosen[slot2[i]] = {t: chosen[slot1[i]][t] for t in range(whole[i])}  # the shared pages' tokens: run once
        cache = copy_in(cache, snaps, jnp.int32(slot2[i]), jnp.int32(entries[(i + 1) % n if fault == "wrong_slot" else i]))
        lg, b, cache = prefill(cache, seq, slot2[i], whole[i], whole[i])
        first.append(lg)
        behind2.append(b)
    got2, fed2, cache = decoded(cache, jnp.stack(first), [len(s) for s in follow], slot2, all_live=fault == "idle_moves")
    finals = [s + [int(f[i]) for f in fed2] for i, s in enumerate(follow)]
    # (7): every expert layer alone on one seeded input of a decode step's rows, the stack read where it lies
    layers7 = cfg.n_layers - cfg.num_dense_layers
    h7 = jnp.asarray(rng.standard_normal((B, cfg.d_model)), jnp.float32).astype(cfg.dtype)

    @jax.jit
    def expert_layer(params, h, index):
        stack = params["expert_ffn"]
        layer = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False),
                             transformer.scanned_leaves(cfg, stack))
        y, routes = transformer.moe_ffn_dropless(cfg, layer, h[None], stack=stack, index=index, routes=True)
        return y[0].astype(jnp.float32), routes

    got7, routes7 = zip(*(expert_layer(params, h7, jnp.int32(j)) for j in range(layers7)))
    got7, h7 = jnp.stack(got7), h7.astype(jnp.float32)
    L = cfg.conv_width
    conv = jnp.swapaxes(cache["conv"], 0, 1).astype(jnp.float32).reshape(B, cfg.conv_layers, L - 1, cfg.d_model)
    held = conv[jnp.asarray(slot1 + slot2)]
    unnamed = float(jnp.abs(jnp.delete(conv, jnp.asarray(slot1 + slot2), axis=0)).max()) if B > 2 * n else 0.0
    behind, behind2 = jnp.stack(behind).astype(jnp.float32), jnp.stack(behind2).astype(jnp.float32)  # [n, 2, V]
    del cache, snaps, conv
    if reference_params is not None:
        params = reference_params()

    model = system.model_module(config)
    ref_logits, _ = model.make_reference(config)
    nd = config["num_dense_layers"]
    seen, differ = {}, []
    want7 = []
    for j in range(layers7):
        ffn = model.reference_layer(params, nd + j, config)[1]
        weights = ref_logits.route(h7, ffn["gate"], ffn["expert_bias"], routes7[j])[0]
        want7.append(ref_logits.expert_ffn(h7, weights, ffn))

    def wanted(seqs, positions, slots):
        logits_, tails = [], []
        for seq, where, slot in zip(seqs, positions, slots):
            T = len(seq)
            padded = np.zeros(-(-T // 512) * 512, np.int32)  # causal: what follows changes nothing before it
            padded[:T] = seq
            theirs = np.stack([chosen[slot][t] for t in range(T)], axis=1)  # [expert layers, T, k]
            pad = np.broadcast_to(np.arange(theirs.shape[-1], dtype=theirs.dtype), (theirs.shape[0], len(padded) - T, theirs.shape[-1]))
            theirs = np.concatenate([theirs, pad], axis=1)

            def router(layer, h, gate, bias):
                """The reference's own selection on the input it has (the
                layers before it routed as the program's): counted against
                the program's, then the program's handed over."""
                if layer == nd and "h" not in seen:
                    seen.update(h=h, gate=gate, bias=bias)
                own = np.sort(np.asarray(ref_logits.route(h, gate, bias)[1])[:T], -1)
                differ.append((own != np.sort(theirs[layer - nd, :T], -1)).any(-1))
                return jnp.asarray(theirs[layer - nd]) if matched else None

            lg, tl = ref_logits(params, jnp.asarray(padded), jnp.asarray(where), tails_after=T, on_router=router)
            logits_.append(lg)
            tails.append(tl)
        return jnp.stack(logits_), jnp.stack(tails)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    def worst_sequence(a, b):
        axes = tuple(range(1, a.ndim))
        return float(jnp.max(jnp.sqrt(jnp.sum((a - b) ** 2, axis=axes) / jnp.sum(b ** 2, axis=axes))))

    # one pass a sequence: the last position's and the decode steps' logits, and the two behind the boundary
    def positions(lens_, boundaries):
        return [list(range(L_ - 1, L_ + k)) + [b, b + 1] for L_, b in zip(lens_, boundaries)]

    want, tails1 = wanted(histories, positions(lens, cuts), slot1)
    want2, tails2 = wanted(finals, positions([len(s) for s in follow], whole), slot2)
    err, err2 = rel(got, want[:, : k + 1]), rel(got2, want2[:, : k + 1])
    boundary = max(rel(behind, want[:, k + 1 :]), rel(behind2, want2[:, k + 1 :]))
    tails = jnp.concatenate([tails1, tails2])                                  # [2n, conv layers, L - 1, d]
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    lead = sum(1 for i, t in enumerate(kinds) if t == "conv" and i <= nd)      # conv layers no expert layer lies before
    expert_err = rel(got7, jnp.stack(want7))
    tails_err = worst_sequence(held, tails)
    lead_err = worst_sequence(held[:, :lead], tails[:, :lead]) if lead else 0.0
    # (5): the program's router on the reference's own input of the first expert layer
    layer = {"router": seen["gate"], "router_bias": seen["bias"]}
    mine = np.sort(np.asarray(jax.jit(lambda layer, h: transformer.route(cfg, layer, h)[0])(layer, seen["h"])), -1)
    theirs = np.sort(np.asarray(ref_logits.route(seen["h"], seen["gate"], seen["bias"])[1]), -1)
    swaps = float((mine != theirs).any(-1).mean())
    finite = bool(jnp.isfinite(got).all() and jnp.isfinite(got2).all())
    copied = float(np.mean([[seq[-j] == seq[-j - 1] for j in range(1, k)] for seq in histories + finals])) if k > 1 else 0.0
    out = {"copied_share": copied, "rel_err": err, "rel_tol": cc["rel_tol"], "restored_rel_err": err2, "restored_rel_tol": cc["restored_rel_tol"],
           "boundary_rel_err": boundary, "boundary_rel_tol": cc["boundary_rel_tol"],
           "tails_rel_err": tails_err, "tails_rel_tol": cc["tails_rel_tol"],
           "lead_tails_rel_err": lead_err, "lead_tails_rel_tol": cc["lead_tails_rel_tol"],
           "router_swap_share": swaps, "router_swap_max": cc["router_swap_max"],
           "expert_rel_err": expert_err, "expert_rel_tol": cc["expert_rel_tol"],
           # of the (token, expert layer) pairs, those the reference alone would have routed otherwise
           "near_tie_share": float(np.concatenate(differ).mean()), "routes_matched": bool(matched),
           "unnamed_slots_max_abs": unnamed, "slots": slot1 + slot2, "snapshot_entries": entries,
           "vectors": int(2 * n * (3 + k)), "prompt_lengths": [int(x) for x in lens], "cuts": cuts, "snapshots_at": whole}
    out["ok"] = bool(finite and unnamed == 0.0 and all(out[name] < out[limit] for name, limit in LIMITS))
    return out


# each number of the runner's check beside the key of its limit
LIMITS = (("rel_err", "rel_tol"), ("restored_rel_err", "restored_rel_tol"), ("boundary_rel_err", "boundary_rel_tol"),
          ("tails_rel_err", "tails_rel_tol"), ("lead_tails_rel_err", "lead_tails_rel_tol"),
          ("router_swap_share", "router_swap_max"), ("expert_rel_err", "expert_rel_tol"))


class ConvServed(serving.Served):
    """``serving.Served`` for a configuration with conv layers: the runner's
    check is the one above, and the engine gets its pool of tail snapshots."""

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
        params = jax.block_until_ready(make_params(self.cfg, config, seed))
        self.params, self.log = params, log
        log(f"weights on the device in {now() - t:.1f} s")
        self.correctness = {}
        if runner_check:
            t = now()
            self.correctness["conv runner"] = check_conv_against_reference(self.cfg, params, config, seed)
            log(f"conv runner against the reference in {now() - t:.1f} s: {self.correctness}")
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

    def _warm(self) -> None:
        """``Served._warm`` (the chunk and decode programs, the samplers, a
        zeroed slot and a snapshot after a prompt of whole pages), then a
        prompt that extends one of them: a slot restored from a snapshot."""
        super()._warm()
        rng = np.random.default_rng(0)  # ``Served._warm``'s own first prompt, and more
        short = rng.integers(1, self.cfg.vocab_size, size=2 * self.run["kv_block_size"]).tolist()
        turn = serving.Turn(now(), short + short[:5], 4, False)
        self.stream(turn, threading.Event())
        if turn.error or len(turn.tokens) != 4 or not self.stats().get("state_restores"):
            raise RuntimeError(f"warm-up of a restored slot failed: {turn.error or self.stats()}")

    def check_served(self, conversations) -> None:
        """``Served.check_served``; it is the last thing a run does before its
        ramp, so what the set-up and the checks allocated is moved out of the
        collector's sight here (``state_sessions.StateServed.check_served``)."""
        super().check_served(conversations)
        gc.collect()
        gc.freeze()


def run(ctx) -> Dict[str, Any]:
    served = ConvServed(ctx.config, ctx.seed, ctx.log)
    try:
        return drive(ctx, served, ctx.traffic, ctx.seconds)
    finally:
        served.close()


def drive(ctx, served: ConvServed, p: Dict[str, Any], seconds: float, seed=None) -> Dict[str, Any]:
    """``open_loop_requests.drive``, and the runner's numbers beside their limits."""
    out = open_loop_requests.drive(ctx, served, p, seconds, seed)
    runner = served.correctness.get("conv runner")
    if runner is not None:
        out["compared"]["conv_rel_err"] = [runner["rel_err"], runner["rel_tol"]]
        for name, limit in LIMITS[1:]:
            out["compared"][name] = [runner[name], runner[limit]]
    return out
