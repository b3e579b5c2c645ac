"""Disaggregated prefill/decode serving tests (serve/disagg.py).

Contracts:
- identity: tokens decoded from a migrated block set are bit-for-bit the
  one-shot reference continuation (the migration moves state, never math)
- control stream is header-only: the ticket carries block-table metadata,
  zero KV payload bytes
- prefix-cache interaction: migrated blocks insert into the decode
  replica's prefix cache on finish, a warm decode prefix short-circuits
  re-migration (only the uncached suffix is pulled), identity holds
- fallback ladder: a released staging surfaces as the typed
  KVMigrationError, never a hang or a silent wrong answer
- deploy-time role validation fails fast with a typed ValueError
- serve stack end-to-end: a ``roles=`` deployment routes prefill by queue
  depth and decode by free KV pages, sync + streaming both work, and every
  staged migration is audited to exactly one terminal
- chaos: a scheduled decode-replica kill walks the re-prefill ladder;
  same-seed runs replay byte-identical fault logs and invariant 13 sweeps
  (every staged block set freed exactly once)
- observability: the ``kv_migrate`` waterfall segment exists and phase
  durations still sum exactly to end-to-end
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_reference import greedy_reference
from ray_tpu.models import TransformerConfig, init_params
from ray_tpu.observability import metric_defs
from ray_tpu.observability.reqtrace import RequestTrace
from ray_tpu.runtime import failpoints
from ray_tpu.serve import disagg
from ray_tpu.serve.disagg import (
    KVMigrationError,
    migration_uuid,
    validate_roles,
)
from ray_tpu.serve.llm import LLMEngine, LLMServer

CFG = TransformerConfig(
    vocab_size=89, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    attention="dense", dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(11))


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


_reference = functools.partial(greedy_reference, CFG)


def _paged(params, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_seq_len", 64)
    return LLMEngine(CFG, params, **kw)


def _wait(pred, timeout=60):
    deadline = time.time() + timeout
    while not pred() and time.time() < deadline:
        time.sleep(0.005)
    assert pred()


def _assert_no_leak(eng):
    """Quiesced-engine leak check under prefix caching: every held page is
    accounted for by the prefix cache, and flushing it empties the pool."""
    st = eng.stats()
    assert st["kv_blocks_in_use"] == st["prefix_cache_blocks"]
    eng.store.flush_prefix_cache()
    st = eng.stats()
    assert st["kv_blocks_in_use"] == 0 and st["prefix_cache_blocks"] == 0


def _migrate(p_eng, d_eng, prompt, mig_id, max_tokens=6):
    """Manual dispatcher: export on the prefill engine, pull only the
    uncached-suffix blocks, adopt on the decode engine.  Returns
    ``(ticket, tokens, rungs)`` where ``rungs`` has one entry per block
    actually pulled (empty on a full decode-side prefix hit)."""
    ticket = p_eng.prefill_export(prompt, mig_id=mig_id).result(timeout=120)
    bs = d_eng.kv_block_size
    matched = d_eng.store.peek_prefix_match(prompt)
    arrays, rungs = {}, []
    for bidx in range(matched // bs, int(ticket["n_blocks"])):
        arr, rung = disagg.pull_block(ticket, bidx)
        arrays[bidx] = arr
        rungs.append(rung)
    req = d_eng.adopt_migration(ticket, arrays, max_tokens=max_tokens)
    out = req.future.result(timeout=120)
    return ticket, out, rungs


# --------------------------------------------------------------------------
# identity + wire format
# --------------------------------------------------------------------------
def test_migration_uuid_derived_never_random():
    a = migration_uuid("LLMServer/m1", 0)
    assert a == migration_uuid("LLMServer/m1", 0)
    assert a != migration_uuid("LLMServer/m1", 1)
    assert a != migration_uuid("LLMServer/m2", 0)
    # low 32 bits carry the block index; never zero (transfer-server uuids)
    assert migration_uuid("LLMServer/m1", 5) & 0xFFFFFFFF == 5
    assert migration_uuid("LLMServer/m1", 0) != 0


def test_ticket_is_header_only(params):
    """Satellite guard: zero KV payload bytes on the control stream — the
    ticket is plain block-table metadata, small and array-free."""
    import json

    p_eng = _paged(params)
    try:
        prompt = list(range(1, 20))  # 19 tokens -> 2 blocks @ block_size=16
        ticket = p_eng.prefill_export(prompt, mig_id="t/hdr").result(timeout=120)
        assert set(ticket) == {
            "mig_id", "prompt", "tok0", "n_blocks", "block_size",
            "block_shape", "block_dtype", "transfer_addr", "data_addr",
            "source",
        }
        for v in ticket.values():
            assert not hasattr(v, "shape") or isinstance(v, tuple)
            assert isinstance(v, (str, int, float, list, tuple, type(None)))
        assert ticket["n_blocks"] == 2 and ticket["block_size"] == 16
        # [2(k,v), L, block_size, Hkv, Dh]
        assert tuple(ticket["block_shape"]) == (2, CFG.n_layers, 16,
                                                CFG.n_kv_heads, 8)
        assert ticket["tok0"] == _reference(params, prompt, 1)[0]
        # header-only really means header-only: a few hundred bytes
        assert len(json.dumps(ticket)) < 2048
        assert p_eng.store.release_migration("t/hdr")
    finally:
        p_eng.shutdown()


def test_migration_bit_identical(params):
    p_eng, d_eng = _paged(params), _paged(params)
    try:
        prompt = [3, 14, 15, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3, 8, 4, 6]
        ref = _reference(params, prompt, 8)
        _, out, rungs = _migrate(p_eng, d_eng, prompt, "t/ident", max_tokens=8)
        assert out == ref
        # no runtime in this test: every pull resolves via the in-process
        # registry rung (reported as the host fallback)
        assert rungs and all(r == "host" for r in rungs)
        st = p_eng.stats()
        assert st["migrations_out"] == 1 and st["staged_migrations"] == 1
        assert d_eng.stats()["migrations_in"] == 1
        # exactly-once: release drops the staging, the second is a no-op
        assert p_eng.store.release_migration("t/ident") is True
        assert p_eng.store.release_migration("t/ident") is False
        _wait(lambda: d_eng.stats()["active_slots"] == 0)
        _assert_no_leak(p_eng)
        _assert_no_leak(d_eng)
    finally:
        p_eng.shutdown()
        d_eng.shutdown()


def test_warm_decode_prefix_short_circuits_re_migration(params):
    p_eng, d_eng = _paged(params), _paged(params)
    try:
        prompt = [(7 * i + 3) % CFG.vocab_size for i in range(32)]  # 2 full blocks
        ref = _reference(params, prompt, 6)

        _, out1, rungs1 = _migrate(p_eng, d_eng, prompt, "t/warm1")
        assert out1 == ref
        assert len(rungs1) == 2  # cold decode side: every block pulled
        assert p_eng.store.release_migration("t/warm1")
        _wait(lambda: d_eng.stats()["active_slots"] == 0)

        # migrated blocks landed in the DECODE replica's prefix cache
        assert d_eng.store.peek_prefix_match(prompt) == 32

        # same prompt again: full prefix hit, ZERO blocks re-migrated,
        # tokens still bit-for-bit
        _, out2, rungs2 = _migrate(p_eng, d_eng, prompt, "t/warm2")
        assert out2 == ref
        assert rungs2 == []
        assert p_eng.store.release_migration("t/warm2")

        # extended prompt: only the uncached suffix block crosses the wire
        prompt3 = prompt + [11, 12, 13, 14, 15, 16, 17, 18]  # 40 -> 3 blocks
        ref3 = _reference(params, prompt3, 5)
        _, out3, rungs3 = _migrate(p_eng, d_eng, prompt3, "t/warm3",
                                   max_tokens=5)
        assert out3 == ref3
        assert len(rungs3) == 1
        assert p_eng.store.release_migration("t/warm3")

        _wait(lambda: d_eng.stats()["active_slots"] == 0)
        _assert_no_leak(p_eng)
        _assert_no_leak(d_eng)
    finally:
        p_eng.shutdown()
        d_eng.shutdown()


def test_released_staging_raises_typed_error(params):
    """Fallback-ladder floor: once the staging is gone and no rung can
    reach it, pull_block raises the typed KVMigrationError (the dispatcher
    turns this into a re-prefill, callers only see it ladder-exhausted)."""
    p_eng = _paged(params)
    try:
        prompt = list(range(2, 21))
        ticket = p_eng.prefill_export(prompt, mig_id="t/gone").result(timeout=120)
        assert p_eng.store.release_migration("t/gone")
        with pytest.raises(KVMigrationError) as exc:
            disagg.pull_block(ticket, 0)
        assert exc.value.mig_id == "t/gone"
        assert exc.value.stage == "staging"
        _assert_no_leak(p_eng)
    finally:
        p_eng.shutdown()


# --------------------------------------------------------------------------
# role validation
# --------------------------------------------------------------------------
def test_validate_roles_typed_errors():
    validate_roles(None)  # homogeneous deployments validate vacuously
    validate_roles({"prefill": 2, "decode": 3})
    with pytest.raises(ValueError, match="unknown deployment role"):
        validate_roles({"prefill": 1, "decode": 1, "draft": 1})
    with pytest.raises(ValueError, match="at least one 'decode'"):
        validate_roles({"prefill": 2})
    with pytest.raises(ValueError, match="at least one 'prefill'"):
        validate_roles({"prefill": 0, "decode": 2})


# --------------------------------------------------------------------------
# observability
# --------------------------------------------------------------------------
def test_disagg_metrics_registered():
    assert metric_defs.LLM_KV_MIGRATIONS in metric_defs.ALL_METRICS
    assert metric_defs.LLM_KV_MIGRATION_SECONDS in metric_defs.ALL_METRICS
    assert metric_defs.SERVE_POOL_REPLICAS in metric_defs.ALL_METRICS
    assert metric_defs.SERVE_POOL_ONGOING in metric_defs.ALL_METRICS


def test_kv_migrate_waterfall_sums_to_e2e():
    """Satellite 1: a disaggregated request's waterfall carries the
    kv_migrate segment, the trailing segment is decode, and phase durations
    still sum exactly to the last mark's offset."""
    tr = RequestTrace(route="/llm", deployment="LLMServer")
    for m in ("router_in", "router_dequeue", "replica_in", "engine_submit",
              "wfq_pop", "admitted", "first_token", "kv_migrate", "finished"):
        tr.mark(m)
    phases = tr.phases()
    names = [p[0] for p in phases]
    assert names == ["proxy", "router_queue", "dispatch", "replica",
                     "engine_queue", "kv_block_wait", "prefill",
                     "kv_migrate", "decode"]
    # contiguous: each segment starts where the previous ended
    for (_, _, end), (_, start, _) in zip(phases, phases[1:]):
        assert end == start
    total = sum(end - start for _, start, end in phases)
    assert total == pytest.approx(tr.mark_offset("finished"))
    # co-located requests (no kv_migrate mark) still sum to e2e and end in
    # decode, so the disagg segment is additive, not a schema fork
    tr2 = RequestTrace(route="/llm", deployment="LLMServer")
    for m in ("router_in", "replica_in", "first_token", "finished"):
        tr2.mark(m)
    p2 = tr2.phases()
    assert p2[-1][0] == "decode"
    assert sum(e - s for _, s, e in p2) == pytest.approx(
        tr2.mark_offset("finished"))


# --------------------------------------------------------------------------
# serve stack end-to-end
# --------------------------------------------------------------------------
def test_serve_disagg_roles_end_to_end(params):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.runtime.worker import global_worker

    ray_tpu.init(num_cpus=4)
    serve.start(http_port=0)
    try:
        # deploy-time validation: typed ValueError, fail fast — never a
        # deployment that wedges at its first migration (the controller
        # raise arrives wrapped in RayTaskError with the original cause)
        from ray_tpu.exceptions import RayTaskError

        def _deploy_must_fail(dep, bind_kwargs, needle):
            with pytest.raises((ValueError, RayTaskError)) as exc:
                serve.run(dep.bind(lambda: (CFG, params), **bind_kwargs),
                          route_prefix=None)
            cause = getattr(exc.value, "cause", exc.value)
            assert isinstance(cause, ValueError), exc.value
            assert needle in str(cause)

        _deploy_must_fail(
            serve.deployment(LLMServer, name="BadRoles",
                             roles={"prefill": 1}),
            {}, "at least one 'decode'")

        app = serve.deployment(
            LLMServer, roles={"prefill": 1, "decode": 1}
        ).bind(lambda: (CFG, params), max_batch_size=4, max_seq_len=64)
        handle = serve.run(app, route_prefix=None)
        # a router serves co-located until the deployment's meta (its
        # roles) has followed its first membership snapshot: requests sent
        # before that stage nothing, and the audit counts below would miss
        # them (under a loaded host the meta call takes longer than the
        # first request)
        handle._router._refresh()
        _wait(lambda: handle._router._roles is not None)

        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3]
        ref = _reference(params, prompt, 6)

        # sync: prefill-pool chunked prefill -> device-plane migration ->
        # decode-pool continuous batching, tokens bit-for-bit
        r = handle.remote({"prompt": prompt, "max_tokens": 6}).result(
            timeout=120)
        assert r["tokens"] == ref and r["num_generated"] == 6

        # streaming rides the same migration path
        events = list(
            handle.remote(
                {"prompt": prompt, "max_tokens": 6, "stream": True}
            ).result(timeout=120)
        )
        toks = [e["token"] for e in events if "token" in e]
        assert toks == ref
        assert events[-1] == {"done": True, "num_generated": 6}

        # warm decode prefix (populated by the requests above) still
        # produces identical output through the serve stack
        r2 = handle.remote({"prompt": prompt, "max_tokens": 6}).result(
            timeout=120)
        assert r2["tokens"] == ref

        # every staged migration reached exactly one terminal, all adopted
        cluster = global_worker().cluster
        audits = list(cluster.kv_migration_audits)
        staged = [a for a in audits if a["event"] == "staged"]
        released = [a for a in audits if a["event"] == "released"]
        assert len(staged) >= 3
        assert sorted(a["mig_id"] for a in staged) == sorted(
            a["mig_id"] for a in released)
        assert all(a["outcome"] == "adopted" for a in released)

        # per-role pools surface in the overload snapshot (rt overload)
        pools = cluster.overload_snapshot()["serve_pools"]["LLMServer"]
        assert set(pools) == {"prefill", "decode"}
        assert pools["prefill"]["replicas"] == 1
        assert pools["decode"]["replicas"] == 1
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# --------------------------------------------------------------------------
# chaos: decode-replica kill -> re-prefill ladder, byte-identical replays
# --------------------------------------------------------------------------
_CHAOS_PROMPT = [5, 3, 7, 1, 9, 2, 8, 4, 6, 1, 2, 3, 4, 5, 6, 7, 8, 9]


def _disagg_chaos_run(seed, params, refs):
    """One seeded chaos run: roles={prefill:1, decode:2}; the schedule
    hard-kills decode replica 0 with traffic in flight (NO failpoint
    decisions consumed — membership perturbation only), then the workload
    arms ``disagg.decode_call=raise(0.4)`` and drives strictly sequential
    requests so every decision-stream index is workload-ordered."""
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.chaos import ChaosEvent, ChaosRunner, ChaosSchedule
    from ray_tpu.runtime.worker import global_worker

    rt.init(num_cpus=4)
    try:
        schedule = ChaosSchedule(
            [
                ChaosEvent(0.8, "kill_decode_replica", deployment="LLMServer",
                           role="decode", index=0),
            ],
            seed=seed, name="disagg-decode-kill",
        )

        def workload():
            t_start = time.monotonic()
            serve.start(http_port=0)
            app = serve.deployment(
                LLMServer, roles={"prefill": 1, "decode": 2}
            ).bind(lambda: (CFG, params), max_batch_size=4, max_seq_len=64)
            handle = serve.run(app, route_prefix=None)
            # the kill at t=0.8 needs a decode pool to aim at
            ctls = list(global_worker().cluster.serve_controllers.values())
            assert ctls, "controller never registered its chaos hook"
            _wait(lambda: ctls[0].pool_status().get("LLMServer", {})
                  .get("decode", {}).get("replicas", 0) >= 2, timeout=30)

            prompt = _CHAOS_PROMPT
            ref = refs[tuple(prompt)]
            # phase 1 — races the scheduled kill: a decode death
            # mid-migration may exhaust the ladder (typed error), anything
            # else must still be the exact reference tokens
            try:
                r = handle.remote({"prompt": prompt, "max_tokens": 4}).result(
                    timeout=60)
                assert r["tokens"] == ref
            except KVMigrationError:
                pass
            # wait out the kill window: the armed phase must see a stable
            # membership (a dead-replica retry would consume an extra
            # failpoint decision and break byte-identity)
            time.sleep(max(0.0, 2.0 - (time.monotonic() - t_start)))

            # phase 2 — deterministic failpoint hits: sequential requests,
            # each route attempt consumes exactly one decision index
            failpoints.arm("disagg.decode_call=raise(0.4)")
            ladder_exhausted = 0
            for i in range(5):
                p = prompt + [i + 1]
                try:
                    r = handle.remote({"prompt": p, "max_tokens": 3}).result(
                        timeout=60)
                    assert r["tokens"] == refs[tuple(p)]
                except KVMigrationError:
                    ladder_exhausted += 1
            # NO disarm here: failpoints.disarm() clears the fault log, and
            # the runner captures it (then disarms) after quiescence
            serve.shutdown()
            return ladder_exhausted

        result = ChaosRunner(schedule, quiesce_timeout=90).run(workload)
        assert result.ok, (result.workload_error,
                           result.invariants.violations)
        kills = [e for e in result.events_applied
                 if e["kind"] == "kill_decode_replica"]
        assert len(kills) == 1 and "skipped" not in kills[0], kills
        # invariant 13 had migrations to sweep: phase 1 + 5 armed requests,
        # each staging at least one block set
        assert result.invariants.checked.get("kv_migrations", 0) >= 5
        return result
    finally:
        rt.shutdown()


@pytest.mark.parametrize("seed", [41])
def test_chaos_decode_replica_kill_byte_identical(seed, params):
    # references precomputed OUTSIDE the runs: the workload's wall-clock
    # shape stays identical across both replays (and the one-shot
    # generate() compiles don't run twice)
    refs = {tuple(_CHAOS_PROMPT): _reference(params, _CHAOS_PROMPT, 4)}
    for i in range(5):
        p = _CHAOS_PROMPT + [i + 1]
        refs[tuple(p)] = _reference(params, p, 3)
    r1 = _disagg_chaos_run(seed, params, refs)
    r2 = _disagg_chaos_run(seed, params, refs)
    assert r1.faults, "the disagg.decode_call failpoint must actually fire"
    assert all(f["fp"] == "disagg.decode_call" for f in r1.faults)
    assert r1.same_faults(r2), (r1.faults, r2.faults)
