"""LLM serving engine tests: greedy engine output == one-shot generate(),
continuous admission (mid-flight joins), slot reuse, eos/max_tokens stops,
and the Serve deployment wrapper."""

import functools
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_reference import greedy_reference
from ray_tpu.models import TransformerConfig, init_params
from ray_tpu.serve.llm import LLMEngine, LLMServer, _bucket

CFG = TransformerConfig(
    vocab_size=89, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    attention="dense", dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(11))


@pytest.fixture()
def engine(params):
    eng = LLMEngine(CFG, params, max_batch_size=4, max_seq_len=64)
    yield eng
    eng.shutdown()


_reference = functools.partial(greedy_reference, CFG)


def test_single_request_matches_generate(engine, params):
    prompt = [3, 14, 15, 9, 2]
    got = engine.generate(prompt, max_tokens=6)
    assert got == _reference(params, prompt, 6)


@pytest.mark.full
def test_concurrent_ragged_requests_match(engine, params):
    prompts = [[5, 6], [7, 8, 9, 10, 11], [1] * 17, [42]]
    futs = [engine.submit(p, max_tokens=5) for p in prompts]
    outs = [f.result(timeout=120) for f in futs]
    for p, o in zip(prompts, outs):
        assert o == _reference(params, p, 5)


@pytest.mark.full
def test_continuous_admission_mid_flight(engine, params):
    """A request submitted while another decodes must join its batch and
    still produce exactly the solo-run tokens."""
    first = engine.submit([2, 3, 4], max_tokens=24)
    time.sleep(0.2)  # let decoding start
    second = engine.submit([9, 8, 7, 6], max_tokens=4)
    assert second.result(timeout=120) == _reference(params, [9, 8, 7, 6], 4)
    assert first.result(timeout=120) == _reference(params, [2, 3, 4], 24)


@pytest.mark.full
def test_slot_reuse_more_requests_than_slots(engine, params):
    prompts = [[i + 1, i + 2] for i in range(9)]  # 9 requests, 4 slots
    futs = [engine.submit(p, max_tokens=3) for p in prompts]
    for p, f in zip(prompts, futs):
        assert f.result(timeout=120) == _reference(params, p, 3)


def test_eos_stops_generation(engine, params):
    prompt = [4, 5, 6]
    ref = _reference(params, prompt, 8)
    eos = ref[2]
    got = engine.generate(prompt, max_tokens=8, eos_id=eos)
    # stops at (and includes) the FIRST occurrence of the eos token
    assert got == ref[: ref.index(eos) + 1]


def test_prompt_too_long_rejected(engine):
    with pytest.raises(ValueError):
        engine.submit(list(range(60)), max_tokens=10)


def test_sampled_temperature_valid_tokens(engine):
    out = engine.generate([1, 2, 3], max_tokens=12, temperature=1.3)
    assert len(out) == 12
    assert all(0 <= t < CFG.vocab_size for t in out)


def test_bucket():
    assert _bucket(1) == 16
    assert _bucket(16) == 16
    assert _bucket(17) == 32
    assert _bucket(100) == 128
    # capped: the bucket clamps to the cache capacity instead of growing
    # past it, and a length that cannot fit raises (never-fits contract)
    assert _bucket(100, cap=128) == 128
    assert _bucket(100, cap=100) == 100
    assert _bucket(64, cap=64) == 64
    with pytest.raises(ValueError):
        _bucket(65, cap=64)


def test_llm_server_deployment(params):
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    serve.start(http_port=0)
    try:
        app = serve.deployment(LLMServer).bind(
            lambda: (CFG, params), max_batch_size=4, max_seq_len=64
        )
        handle = serve.run(app, route_prefix=None)
        reqs = [{"prompt": [3, 1, 4], "max_tokens": 5}, {"prompt": [2, 7], "max_tokens": 3}]
        resps = [handle.remote(r) for r in reqs]
        r0, r1 = (r.result() for r in resps)
        assert r0["tokens"] == _reference(params, [3, 1, 4], 5)
        assert r1["tokens"] == _reference(params, [2, 7], 3)
        assert r0["num_generated"] == 5
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_submit_after_shutdown_raises(params):
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=32)
    eng.shutdown()
    with pytest.raises(RuntimeError):
        eng.submit([1, 2], max_tokens=2)


def test_zero_max_tokens_rejected(engine):
    with pytest.raises(ValueError):
        engine.submit([1, 2], max_tokens=0)


@pytest.mark.full
def test_quantized_engine_generates(params):
    """Weight-only int8 engine: layer linears stored int8 (norm gains stay
    fp), greedy output EXACTLY matches generate() on the dequantized
    weights (in-scan dequant is numerically the same computation)."""
    import jax.numpy as jnp2

    from ray_tpu.ops.quantization import dequantize_int8

    eng_q = LLMEngine(
        CFG, params, max_batch_size=2, max_seq_len=64, quantize=True, quantize_min_size=256
    )
    try:
        q_layers = eng_q.runner.params["layers"]
        assert q_layers["wq"].dtype == jnp2.int8
        assert q_layers["attn_norm"].dtype == CFG.param_dtype  # norms untouched
        prompt = [3, 14, 15]
        q_out = eng_q.generate(prompt, max_tokens=8)

        deq_layers = {
            k: (
                dequantize_int8(w, eng_q.runner._layer_scales[k], CFG.param_dtype)
                if w.dtype == jnp2.int8
                else w
            )
            for k, w in q_layers.items()
        }
        ref_params = {**eng_q.runner.params, "layers": deq_layers}
        assert q_out == _reference(ref_params, prompt, 8)
    finally:
        eng_q.shutdown()


def test_train_then_serve_e2e():
    """The round-trip story: train a tiny LM with the sharded train step,
    then serve the trained weights through the continuous-batching engine."""
    import jax
    import jax.numpy as jnp2

    from ray_tpu.models import make_train_step

    cfg = TransformerConfig(
        vocab_size=32, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        attention="dense", dtype=jnp2.float32,
    )
    init_state, step = make_train_step(cfg, learning_rate=5e-2)
    state = init_state(jax.random.key(0))
    # the "dataset": sequences counting upward — learnable in a few steps
    base = np.arange(18) % 32
    batch = jnp2.asarray(np.stack([np.roll(base, -i) for i in range(8)]), jnp2.int32)
    first = None
    for _ in range(30):
        state, loss = step(state, batch)
        first = first if first is not None else float(loss)
    assert float(loss) < first  # it learned something

    eng = LLMEngine(cfg, state["params"], max_batch_size=2, max_seq_len=32)
    try:
        out = eng.generate([0, 1, 2, 3], max_tokens=4)
        assert out == [4, 5, 6, 7], out  # continues the learned sequence
    finally:
        eng.shutdown()


@pytest.mark.full
def test_submit_stream_tokens_arrive_incrementally(engine, params):
    """Streaming yields the same tokens as the blocking API, and the first
    token arrives before the request completes."""
    prompt = [5, 6, 7]
    ref = _reference(params, prompt, 6)
    got = list(engine.submit_stream(prompt, max_tokens=6))
    assert got == ref


def test_stream_interleaves_with_blocking(engine, params):
    it = engine.submit_stream([2, 3], max_tokens=10)
    blocking = engine.submit([4, 5], max_tokens=4)
    streamed = list(it)
    assert streamed == _reference(params, [2, 3], 10)
    assert blocking.result(timeout=120) == _reference(params, [4, 5], 4)


@pytest.mark.full
def test_http_sse_streaming(params):
    import urllib.request

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    serve.start(http_port=0)
    try:
        app = serve.deployment(LLMServer).bind(
            lambda: (CFG, params), max_batch_size=2, max_seq_len=64
        )
        serve.run(app, route_prefix="/llm")
        body = json.dumps({"prompt": [3, 1, 4], "max_tokens": 5, "stream": True}).encode()
        req = urllib.request.Request(
            serve.proxy_url() + "/llm", data=body,
            headers={"Content-Type": "application/json"},
        )
        resp = urllib.request.urlopen(req, timeout=120)
        assert resp.headers["Content-Type"] == "text/event-stream"
        events = []
        for line in resp:
            line = line.decode().strip()
            if line.startswith("data: "):
                events.append(json.loads(line[6:]))
        toks = [e["token"] for e in events if "token" in e]
        assert toks == _reference(params, [3, 1, 4], 5)
        assert events[-1] == {"done": True, "num_generated": 5}
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_stream_validation_error_raises_eagerly(engine):
    """submit_stream validates BEFORE returning the iterator."""
    with pytest.raises(ValueError):
        engine.submit_stream(list(range(60)), max_tokens=20)


def test_http_sse_invalid_request_gets_error_response(params):
    import urllib.request

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    serve.start(http_port=0)
    try:
        app = serve.deployment(LLMServer).bind(
            lambda: (CFG, params), max_batch_size=2, max_seq_len=32
        )
        serve.run(app, route_prefix="/llm2")
        body = json.dumps(
            {"prompt": [1, 2, 3], "max_tokens": 500, "stream": True}  # > max_seq_len
        ).encode()
        req = urllib.request.Request(
            serve.proxy_url() + "/llm2", data=body,
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=60)
        assert exc.value.code == 500  # clean error status, not a broken 200 stream
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _tp_mesh(n):
    if len(jax.devices()) < n:
        pytest.skip("needs virtual devices")
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("tp",))


@pytest.mark.parametrize("chunk", [0, 8])
def test_mesh_sharded_engine(params, chunk):
    """Tensor-parallel engine over the virtual device mesh: params shard
    per the Megatron layout, the pool's heads over tp, and the same prefill
    program runs, one-shot or in chunks shorter than the prompt: outputs
    match the single-device engine and ``generate()``."""
    mesh = _tp_mesh(2)  # kv_heads=2 -> tp=2 shards kv
    kw = dict(max_batch_size=2, max_seq_len=64, prefill_chunk_tokens=chunk)
    eng_m = LLMEngine(CFG, params, mesh=mesh, **kw)
    eng_s = LLMEngine(CFG, params, **kw)
    try:
        # params really sharded over tp
        wq_sh = eng_m.runner.params["layers"]["wq"].sharding
        assert wq_sh.spec[2] == "tp"
        prompts = [[3, 14, 15], [7, 8], list(range(1, 20))]
        m_out = [eng_m.generate(p, max_tokens=6) for p in prompts]
        s_out = [eng_s.generate(p, max_tokens=6) for p in prompts]
        assert m_out == s_out == [_reference(params, p, 6) for p in prompts]
        # 19 tokens: one bucketed call, or 8 + 8 + 3
        assert eng_m.stats()["prefill_chunks"] == eng_s.stats()["prefill_chunks"] == (5 if chunk else 3)
    finally:
        eng_m.shutdown()
        eng_s.shutdown()


def test_mesh_engine_serves_a_repeated_prompt_from_the_prefix_cache(params):
    """Under a mesh the second serve of a prompt shares the first one's
    pages: no chunk of the prompt is prefilled again (one chunk recomputes
    its last token, whose logits seed sampling, into a copied page), and the
    tokens are the first serve's and ``generate()``'s."""
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64, mesh=_tp_mesh(2), prefill_chunk_tokens=8)
    try:
        prompt = list(range(1, 33))  # two full pages, four chunks
        first = eng.generate(prompt, max_tokens=6)
        cold = eng.stats()
        assert (cold["prefill_chunks"], cold["prefix_cache_hits"]) == (4, 0)
        assert eng.generate(prompt, max_tokens=6) == first == _reference(params, prompt, 6)
        warm = eng.stats()
        assert warm["prefix_cache_hits"] == 1 and warm["cow_copies"] == 1
        assert warm["prefill_chunks"] == cold["prefill_chunks"] + 1
        assert warm["prefix_tokens_reused"] == len(prompt) - 1
    finally:
        eng.shutdown()


@pytest.mark.parametrize("tp,heads", [(2, 1), (4, 2)])
def test_mesh_engine_pool_keeps_whole_pages_of_its_heads_on_each_device(params, tp, heads):
    """tp=2 splits the pool's two KV heads, tp=4 does not divide them and
    leaves it replicated; either way a device holds every page, and the
    programs that return the pool (prefill, decode step, copy-on-write)
    return it laid out as it was."""
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64, mesh=_tp_mesh(tp))
    try:
        def shards():
            return {tuple(sh.data.shape) for kk in ("k", "v") for sh in eng.runner.cache[kk].addressable_shards}

        want = {(CFG.n_layers, eng.kv_num_blocks, eng.kv_block_size, heads * CFG.head_dim)}
        assert shards() == want
        placed = eng.runner.cache["k"].sharding
        prompt = list(range(1, 33))
        eng.generate(prompt, max_tokens=4)
        eng.generate(prompt, max_tokens=4)  # full hit: the tail page is copied
        st = eng.stats()
        assert st["decode_steps"] >= 6 and st["cow_copies"] == 1
        assert shards() == want and eng.runner.cache["k"].sharding == placed
    finally:
        eng.shutdown()


def test_mesh_role_rejected(params):
    with pytest.raises(ValueError, match="role='decode' with mesh"):
        LLMEngine(CFG, params, mesh=_tp_mesh(2), role="decode")


def test_mesh_engine_kv_replicated_when_indivisible(params):
    if len(jax.devices()) < 4:
        pytest.skip("needs virtual devices")
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))  # kv_heads=2, tp=4 -> replicate kv
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=48, mesh=mesh)
    try:
        out = eng.generate([5, 6, 7], max_tokens=4)
        assert len(out) == 4
    finally:
        eng.shutdown()


def test_mesh_quantize_rejected(params):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError):
        LLMEngine(CFG, params, mesh=mesh, quantize=True)


def test_mesh_moe_engine(params):
    """MoE + mesh: expert specs fold ep into tp without duplicate-axis
    crashes (fit_spec keeps the first occurrence)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs virtual devices")
    from jax.sharding import Mesh

    from ray_tpu.models import init_params as ip

    moe_cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        num_experts=2, expert_top_k=1, attention="dense", dtype=jnp.float32,
    )
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    eng = LLMEngine(moe_cfg, ip(moe_cfg, jax.random.key(5)), max_batch_size=2, max_seq_len=32, mesh=mesh)
    try:
        out = eng.generate([1, 2, 3], max_tokens=3)
        assert len(out) == 3
    finally:
        eng.shutdown()


def test_mesh_dropless_expert_engine_returns_counts_beside_the_pool():
    """A windowed config with a dropless routed layer under a mesh: the
    programs return the expert counts as one more output behind the pinned
    pool, and the tokens are ``generate()``'s, cold and from cached pages."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        dtype=jnp.float32, tie_embeddings=False, layer_types=("sliding", "full", "sliding"), sliding_window=16,
        num_experts=8, expert_top_k=2, num_dense_layers=1, expert_d_ff=32, num_shared_experts=1,
        router_score="sigmoid", route_scale=2.0, router_bias=True)
    assert cfg.dropless
    moe_params = init_params(cfg, jax.random.key(0))
    eng = LLMEngine(cfg, moe_params, max_batch_size=2, max_seq_len=64, mesh=_tp_mesh(2), prefill_chunk_tokens=8)
    try:
        prompt = list(range(1, 33))
        want = greedy_reference(cfg, moe_params, prompt, 6)
        assert eng.generate(prompt, max_tokens=6) == want
        assert eng.generate(prompt, max_tokens=6) == want
        st = eng.stats()
        assert st["moe_assignments"] > 0 and st["prefix_cache_hits"] == 1
        assert eng.runner.cache["k"].sharding.spec[3] == "tp"
    finally:
        eng.shutdown()


@pytest.mark.full
def test_text_requests_with_tokenizer(params):
    """model_factory may return (cfg, params, tokenizer): requests send
    'text', responses carry decoded text."""

    class ByteTok:
        def encode(self, s):
            return [b % CFG.vocab_size for b in s.encode()]

        def decode(self, ids):
            return "".join(chr(97 + (i % 26)) for i in ids)

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    serve.start(http_port=0)
    try:
        app = serve.deployment(LLMServer, name="txt").bind(
            lambda: (CFG, params, ByteTok()), max_batch_size=2, max_seq_len=64
        )
        handle = serve.run(app, route_prefix=None)
        r = handle.remote({"text": "hi", "max_tokens": 4}).result()
        assert r["tokens"] == _reference(params, ByteTok().encode("hi"), 4)
        assert r["text"] == ByteTok().decode(r["tokens"])
        # prompt ids still work on the same deployment
        r2 = handle.remote({"prompt": [1, 2], "max_tokens": 3}).result()
        assert len(r2["tokens"]) == 3
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_text_without_tokenizer_rejected(params):
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    serve.start(http_port=0)
    try:
        app = serve.deployment(LLMServer, name="notok").bind(
            lambda: (CFG, params), max_batch_size=2, max_seq_len=32
        )
        handle = serve.run(app, route_prefix=None)
        with pytest.raises(Exception, match="tokenizer"):
            handle.remote({"text": "hi", "max_tokens": 2}).result()
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_llm_server_mesh_passthrough(params):
    """serve deployments reach the tensor-parallel engine path."""
    if len(jax.devices()) < 2:
        pytest.skip("needs virtual devices")
    from jax.sharding import Mesh

    import ray_tpu
    from ray_tpu import serve

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    ray_tpu.init(num_cpus=4)
    serve.start(http_port=0)
    try:
        app = serve.deployment(LLMServer, name="tp_llm").bind(
            lambda: (CFG, params), max_batch_size=2, max_seq_len=48, mesh=mesh
        )
        handle = serve.run(app, route_prefix=None)
        r = handle.remote({"prompt": [3, 14, 15], "max_tokens": 4}).result()
        assert r["tokens"] == _reference(params, [3, 14, 15], 4)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


@pytest.mark.full
def test_data_batch_inference(params):
    """Dataset map_batches with LLMPredictor: offline batch generation
    rides the continuous-batching engine; outputs match solo runs."""
    import ray_tpu
    import ray_tpu.data as rd
    from ray_tpu.data import LLMPredictor

    prompts = [[3, 1], [4, 1, 5], [9, 2], [6, 5, 3, 5]]
    ray_tpu.init(num_cpus=4)
    try:
        ds = rd.from_items([{"prompt": p} for p in prompts])
        factory = lambda: (CFG, params)  # noqa: E731
        out = ds.map_batches(
            LLMPredictor,
            fn_constructor_args=(factory,),
            fn_constructor_kwargs={
                "max_tokens": 4, "max_batch_size": 4, "max_seq_len": 32,
            },
            batch_size=4,
        ).take_all()
        by_prompt = {tuple(r["prompt"]): list(r["generated"]) for r in out}
        for p in prompts:
            assert by_prompt[tuple(p)] == _reference(params, p, 4)
    finally:
        ray_tpu.shutdown()


def test_llm_predictor_cache_respects_kwargs(params):
    """Different engine kwargs must not share a cached engine; same
    factory+kwargs must reuse one."""
    from ray_tpu.data.llm_inference import LLMPredictor, clear_engine_cache

    factory = lambda: (CFG, params)  # noqa: E731
    try:
        a = LLMPredictor(factory, max_batch_size=2, max_seq_len=32)
        b = LLMPredictor(factory, max_batch_size=2, max_seq_len=32)
        c = LLMPredictor(factory, max_batch_size=2, max_seq_len=48)
        assert a.engine is b.engine
        assert a.engine is not c.engine
        assert c.engine.S == 48
    finally:
        clear_engine_cache()  # the supported release API


@pytest.mark.full
def test_llm_bench_script_tiny(monkeypatch, tmp_path):
    """The decode-throughput bench script measures real waves end-to-end
    (tiny config; same warmup/accounting paths as the serving-scale run)."""
    monkeypatch.setenv("RAY_TPU_LLM_BENCH_TINY", "1")
    from ray_tpu.scripts.llm_bench import main

    out = main(str(tmp_path / "llm.json"))
    assert out["metric"] == "llm_decode_throughput"
    assert out["value"] > 0
    assert out["extra"]["total_tokens"] == 2 * 4 * 3  # slots x tokens x waves
    assert (tmp_path / "llm.json").exists()


# ---------------------------------------------------------------------------
# chunked decode (decode_chunk > 1): K tokens per host round trip
# ---------------------------------------------------------------------------
def test_chunked_engine_matches_generate(params):
    eng = LLMEngine(CFG, params, max_batch_size=4, max_seq_len=64, decode_chunk=3)
    try:
        prompt = [3, 14, 15, 9, 2]
        # 8 tokens with K=3: 1 at admission + 3 + 3 + 1-of-3 — the request
        # finishes mid-chunk and the 2 tail tokens are discarded
        assert eng.generate(prompt, max_tokens=8) == _reference(params, prompt, 8)
    finally:
        eng.shutdown()


def test_chunked_engine_concurrent_ragged(params):
    eng = LLMEngine(CFG, params, max_batch_size=4, max_seq_len=64, decode_chunk=4)
    try:
        prompts = [[5, 6], [7, 8, 9, 10, 11], [1] * 17, [42], [13, 12, 11]]
        ns = [9, 5, 7, 11, 6]  # ragged lengths, several mid-chunk finishes
        futs = [eng.submit(p, max_tokens=n) for p, n in zip(prompts, ns)]
        got = [f.result(timeout=120) for f in futs]
        for p, n, g in zip(prompts, ns, got):
            assert g == _reference(params, p, n)
    finally:
        eng.shutdown()


def test_chunked_engine_eos_mid_chunk(params):
    # eos = the SECOND greedy token: the first comes from prefill at
    # admission, so this eos fires at k=0 INSIDE a 4-token decode chunk —
    # the request must stop there and the chunk's 3 tail tokens discard
    # find a prompt whose first two greedy tokens differ, so eos=t2 cannot
    # fire at admission (t1 from prefill) and must fire INSIDE the chunk
    for seed in range(1, 40):
        prompt = [seed, (seed * 7) % 88 + 1, (seed * 3) % 88 + 1]
        t1, t2 = _reference(params, prompt, 2)
        if t1 != t2:
            break
    assert t1 != t2
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64, decode_chunk=4)
    try:
        got = eng.generate(prompt, max_tokens=10, eos_id=t2)
        assert got == [t1, t2]
        # the slot is reusable afterwards: a second request still works
        assert eng.generate(prompt, max_tokens=3) == _reference(params, prompt, 3)
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# OpenAI-compatible adapter (body-shape dispatch; beyond reference parity)
# ---------------------------------------------------------------------------
class _Tok:
    """Toy tokenizer: 1 char = 1 id (offset so ids stay in-vocab)."""

    def encode(self, s):
        return [ord(c) % 80 + 1 for c in s]

    def decode(self, ids):
        return "".join(chr((i - 1) % 80 + 97) for i in ids)


@pytest.fixture()
def oai(params):
    from ray_tpu.serve.openai_compat import OpenAICompatLLMServer

    srv = OpenAICompatLLMServer(
        lambda: (CFG, params, _Tok()), max_batch_size=4, max_seq_len=64
    )
    yield srv
    srv.engine.shutdown()


def test_openai_completions_envelope(oai, params):
    body = {"model": "m", "prompt": "hi", "max_tokens": 5, "temperature": 0}
    resp = oai(body)
    assert resp["object"] == "text_completion" and resp["id"].startswith("cmpl-")
    ch = resp["choices"][0]
    want = _reference(params, _Tok().encode("hi"), 5)
    assert ch["token_ids"] == want and ch["finish_reason"] == "length"
    assert resp["usage"] == {"prompt_tokens": 2, "completion_tokens": 5,
                             "total_tokens": 7}
    # token-id prompts skip the tokenizer entirely
    resp2 = oai({"model": "m", "prompt": [3, 1, 4], "max_tokens": 3, "temperature": 0})
    assert resp2["choices"][0]["token_ids"] == _reference(params, [3, 1, 4], 3)


def test_openai_chat_and_streaming(oai, params):
    body = {"model": "m", "messages": [{"role": "user", "content": "yo"}],
            "max_tokens": 4}
    resp = oai(body)
    assert resp["object"] == "chat.completion"
    msg = resp["choices"][0]["message"]
    assert msg["role"] == "assistant" and isinstance(msg["content"], str)
    # streaming chunks end with a finish_reason frame
    chunks = list(oai({**body, "stream": True}))
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    assert all(c["object"] == "chat.completion.chunk" for c in chunks)
    deltas = [c["choices"][0]["delta"].get("content") for c in chunks[:-1]]
    assert all(isinstance(d, str) for d in deltas)
    assert len(deltas) == 4


def test_openai_stop_token_and_legacy_dispatch(oai, params):
    prompt = [3, 14, 15, 9, 2]
    t1, t2 = _reference(params, prompt, 2)
    resp = oai({"model": "m", "prompt": prompt, "max_tokens": 8, "stop": int(t2),
                "temperature": 0})
    ch = resp["choices"][0]
    if t1 != t2:
        # OpenAI semantics: the stop token is EXCLUDED from the output
        assert ch["token_ids"] == [t1] and ch["finish_reason"] == "stop"
    # streaming also excludes the stop token and reports finish "stop"
    chunks = list(oai({"model": "m", "prompt": prompt, "max_tokens": 8,
                       "stop": int(t2), "stream": True, "temperature": 0}))
    if t1 != t2:
        assert chunks[-1]["choices"][0]["finish_reason"] == "stop"
        toks = [c["choices"][0]["token_ids"][0] for c in chunks[:-1]]
        assert toks == [t1]
    # multi-token stop strings can't stream: clear error, not silent drop
    with pytest.raises(ValueError, match="stop"):
        oai({"model": "m", "prompt": "ab", "max_tokens": 4,
             "stop": "xyz", "stream": True})
    # a body without model/messages takes the native protocol path
    native = oai({"prompt": prompt, "max_tokens": 3})
    assert native["tokens"] == _reference(params, prompt, 3)


def test_openai_over_http(params):
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.openai_compat import OpenAICompatLLMServer

    ray_tpu.init(num_cpus=4)
    serve.start(http_port=0)
    try:
        app = serve.deployment(OpenAICompatLLMServer).bind(
            lambda: (CFG, params, _Tok()), max_batch_size=2, max_seq_len=64
        )
        serve.run(app, route_prefix="/v1")
        body = json.dumps({"model": "m", "prompt": "ab", "max_tokens": 4,
                           "temperature": 0}).encode()
        req = urllib.request.Request(
            serve.proxy_url() + "/v1/completions", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            resp = json.loads(r.read())
        assert resp["object"] == "text_completion"
        assert resp["choices"][0]["token_ids"] == _reference(
            params, _Tok().encode("ab"), 4)
        assert resp["usage"]["completion_tokens"] == 4
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_openai_multi_token_stop_trims_token_ids_too(oai, params):
    """token_ids/usage must describe the trimmed text when a multi-token
    stop string fires, not the raw generation."""
    # this prompt's greedy continuation changes token mid-way, giving a
    # 2-char window usable as a mid-text stop (probed: [5,6] -> ffff}}}})
    prompt = [5, 6]
    raw = _reference(params, prompt, 8)
    text = _Tok().decode(raw)
    # any 2-char window whose FIRST occurrence is mid-text works as a stop
    stop = None
    for i in range(1, len(text) - 1):
        if text.find(text[i : i + 2]) == i:
            stop = text[i : i + 2]
            break
    if stop is None:
        pytest.skip("greedy continuation has no mid-text 2-char stop here")
    resp = oai({"model": "m", "prompt": prompt, "max_tokens": 8, "stop": stop,
                "temperature": 0})
    ch = resp["choices"][0]
    assert ch["finish_reason"] == "stop"
    # token_ids are a faithful prefix of the actual generation, and the
    # text is their decode — envelope self-consistent
    assert ch["token_ids"] == raw[: len(ch["token_ids"])]
    assert ch["text"] == _Tok().decode(ch["token_ids"])
    assert stop not in ch["text"]
    assert resp["usage"]["completion_tokens"] == len(ch["token_ids"])


# ---------------------------------------------------------------------------
# prefix-aware KV reuse (serve/prefix_cache.py; beyond reference parity)
# ---------------------------------------------------------------------------
def test_prefix_cache_reuses_repeat_prompt_blocks(params):
    """A repeated prompt's full blocks come out of the prefix cache: the
    warm run reuses KV (prefix_tokens_reused grows) and is token-identical
    to the cold run under greedy decoding."""
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64,
                    kv_block_size=8)
    try:
        prompt = list(range(1, 18))  # 17 tokens -> 2 full blocks of 8
        want = _reference(params, prompt, 5)
        assert eng.generate(prompt, max_tokens=5) == want
        st = eng.stats()
        assert st["prefix_cache_misses"] == 1 and st["prefix_cache_blocks"] > 0
        assert eng.generate(prompt, max_tokens=5) == want
        st = eng.stats()
        assert st["prefix_cache_hits"] == 1
        assert st["prefix_tokens_reused"] >= 16  # both full blocks skipped
        # a different prompt is a miss and still decodes correctly
        other = [7, 8, 9]
        assert eng.generate(other, max_tokens=4) == _reference(params, other, 4)
        assert eng.stats()["prefix_cache_misses"] == 2
    finally:
        eng.shutdown()


def test_prefix_cache_on_by_default_and_disable_knob(params):
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64)
    off = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64,
                    prefix_cache=False)
    try:
        assert eng.stats()["prefix_cache_enabled"] is True
        assert off.stats()["prefix_cache_enabled"] is False
        p = list(range(1, 20))
        want = _reference(params, p, 3)
        for e in (eng, off):
            assert e.generate(p, max_tokens=3) == want
            assert e.generate(p, max_tokens=3) == want
        # disabled: nothing retained, every page back in the pool
        st = off.stats()
        assert st["prefix_cache_blocks"] == 0 and st["kv_blocks_in_use"] == 0
        assert st["prefix_cache_hits"] == 0
    finally:
        eng.shutdown()
        off.shutdown()


def test_tp_engine_with_chunked_decode(params):
    """decode_chunk composes with tensor-parallel serving: the sharded scan
    program produces ``generate()``'s tokens, cold and from cached pages."""
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64,
                    mesh=_tp_mesh(2), decode_chunk=3)
    try:
        assert eng.stats()["prefix_cache_enabled"] is True
        prompt = [3, 14, 15, 9, 2]
        want = _reference(params, prompt, 7)
        assert eng.generate(prompt, max_tokens=7) == want
        assert eng.generate(prompt, max_tokens=7) == want
    finally:
        eng.shutdown()


def test_openai_absent_temperature_defaults_to_sampling(oai):
    """OpenAI semantics: a body without temperature means 1.0 (sampling),
    NOT greedy — the engine must receive 1.0, and an explicit 0 must still
    reach it untouched."""
    captured = {}
    orig = oai.engine.generate

    def spy(prompt, **kw):
        captured["temperature"] = kw.get("temperature")
        return orig(prompt, **kw)

    oai.engine.generate = spy
    try:
        oai({"model": "m", "prompt": [1, 2], "max_tokens": 2})
        assert captured["temperature"] == 1.0
        oai({"model": "m", "prompt": [1, 2], "max_tokens": 2, "temperature": 0})
        assert captured["temperature"] == 0.0
    finally:
        oai.engine.generate = orig


def test_openai_rejects_unsupported_sampling_params(oai, params):
    base = {"model": "m", "prompt": [1, 2], "max_tokens": 2}
    # OpenAI-SDK defaults sail through
    ok = oai({**base, "top_p": 1.0, "n": 1, "presence_penalty": 0,
              "frequency_penalty": 0.0})
    assert ok["object"] == "text_completion"
    for extra, match in [({"top_p": 0.5}, "top_p"), ({"n": 3}, "n > 1"),
                         ({"logprobs": 5}, "logprobs"),
                         ({"logprobs": 0}, "logprobs"),  # 0 == False trap
                         ({"presence_penalty": 0.7}, "presence_penalty"),
                         ({"echo": True}, "echo")]:
        with pytest.raises(ValueError, match=match.split()[0]):
            oai({**base, **extra})


def test_openai_top_p_allowed_when_engine_configured(params):
    from ray_tpu.serve.openai_compat import OpenAICompatLLMServer

    srv = OpenAICompatLLMServer(
        lambda: (CFG, params, _Tok()), max_batch_size=2, max_seq_len=64,
        top_p=0.9,
    )
    try:
        resp = srv({"model": "m", "prompt": [1, 2], "max_tokens": 2, "top_p": 0.9})
        assert resp["object"] == "text_completion"
        # the SDK default passes, but a DIFFERENT distribution is refused
        srv({"model": "m", "prompt": [1, 2], "max_tokens": 2, "top_p": 1.0})
        with pytest.raises(ValueError, match="top_p=0.2"):
            srv({"model": "m", "prompt": [1, 2], "max_tokens": 2, "top_p": 0.2})
    finally:
        srv.engine.shutdown()


# --------------------------------------------------------------------------
# one decode step in flight: step N+1 is dispatched before step N is read
# --------------------------------------------------------------------------
@pytest.mark.parametrize("K", [1, 4])
def test_staggered_joins_and_finishes_match_reference(params, K):
    """Seven requests through three slots, joining while steps are in flight
    and finishing at different ``max_tokens`` (one at its first token, some
    mid-chunk): every one gets the reference's tokens, and since a
    ``max_tokens`` finish is known by count no row-step is computed in vain."""
    eng = LLMEngine(CFG, params, max_batch_size=3, max_seq_len=64, decode_chunk=K)
    try:
        script = [([5, 6], 19, 0.0), ([7, 8, 9, 10, 11], 3, 0.02), ([1] * 17, 12, 0.0), ([42], 1, 0.03),
                  ([13, 12, 11], 7, 0.0), ([9, 9, 2], 2, 0.02), ([3, 14, 15], 25, 0.0)]
        futs = []
        for prompt, n, pause in script:
            time.sleep(pause)
            futs.append(eng.submit(prompt, max_tokens=n))
        for (prompt, n, _), fut in zip(script, futs):
            assert fut.result(timeout=120) == _reference(params, prompt, n)
        st = eng.stats()
        assert st["decode_row_steps_discarded"] == 0
        assert 0 < st["decode_steps_overlapped"] <= st["decode_steps"]
        assert st["active_slots"] == 0
    finally:
        eng.shutdown()


@pytest.mark.parametrize("K", [1, 4])
def test_overlap_counters_count_steps_dispatched_before_the_last_was_read(params, K):
    eng = LLMEngine(CFG, params, max_batch_size=4, max_seq_len=128, decode_chunk=K)
    try:
        def counters():
            st = eng.stats()
            return st["decode_steps"], st["decode_steps_overlapped"], st["decode_row_steps_discarded"]

        assert eng.generate([5, 6], max_tokens=1) == _reference(params, [5, 6], 1)
        assert counters() == (0, 0, 0)  # a one-token request never decodes
        assert eng.generate([5, 6], max_tokens=2) == _reference(params, [5, 6], 2)
        assert counters() == (K, 0, 0)  # one dispatch, into an empty device, and known to be the last
        prompts = [[2, 3, 4], [7, 8, 9, 10, 11], [40, 41]]
        futs = [eng.submit(p, max_tokens=100) for p in prompts]
        for p, fut in zip(prompts, futs):
            assert fut.result(timeout=120) == _reference(params, p, 100)
        steps, overlapped, discarded = counters()
        assert steps >= K + 99 and discarded == 0
        assert overlapped / steps > 0.9  # a long steady batch: all but the first step of it
    finally:
        eng.shutdown()


def test_an_eos_is_read_one_step_late_and_nothing_follows_it(params):
    """Streaming: the tokens up to the EOS arrive, then the end mark; the
    row-step computed past it is counted and dropped, not emitted."""
    from llm_reference import late_eos_case

    prompt, out, j = late_eos_case(CFG, params)
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64)
    try:
        assert list(eng.submit_stream(prompt, max_tokens=40, eos_id=out[j])) == out[: j + 1]
        # the stream ends where the EOS is read; the step behind it is read next
        deadline = time.time() + 60
        while eng.stats()["decode_row_steps_discarded"] == 0 and time.time() < deadline:
            time.sleep(0.005)
        st = eng.stats()
        assert st["decode_row_steps_discarded"] == 1 and st["active_slots"] == 0
        assert st["decode_steps"] == j + 1  # j tokens read and one step dropped
        # the freed slot's next owner is not handed the dropped row
        assert eng.generate(prompt, max_tokens=j + 3) == out[: j + 3]
    finally:
        eng.shutdown()


def test_lowered_decode_text_is_the_program_the_loop_dispatches(engine):
    """What ``chip_smoke.py`` reads the attention path from: the decode
    program with its eight arguments, returning the ``[B, K]`` tokens for the
    host and the ``[B]`` last tokens that stay on the device."""
    import re

    text = engine.runner.lowered_decode_text()
    main = re.search(r"func\.func public @main\((.*?)\) -> \((.*?)\) \{", text, re.S)
    assert main is not None
    assert "tensor<4x1xi32>" in main.group(2) and "tensor<4xi32>" in main.group(2)
    # toks, join, pos are three int32[B] arguments; temps a float32[B]
    assert main.group(1).count("tensor<4xi32>") == 3 and main.group(1).count("tensor<4xf32>") == 1


def _prefill_kv(eng):
    st = eng.stats()
    return st["prefill_chunks"], st["prefill_kv_tokens_visited"], st["prefill_kv_tokens_capacity"]


def test_prefill_kv_counters_follow_chunks_and_prefix_hits(params):
    """``prefill_kv_tokens_visited``: what a chunk's attention has to visit
    (its start + its new tokens); ``prefill_kv_tokens_capacity``: what the
    table spans, once a chunk. By hand for a three-chunk prompt, a partial
    prefix hit and a full one (one token recomputed)."""
    eng = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64, kv_block_size=8, prefill_chunk_tokens=8)
    try:
        assert _prefill_kv(eng) == (0, 0, 0)
        prompt = list(range(1, 21))  # 20 tokens: chunks at 0, 8 and 16 of 8, 8 and 4
        assert eng.generate(prompt, max_tokens=2) == _reference(params, prompt, 2)
        assert _prefill_kv(eng) == (3, 8 + 16 + 20, 3 * 64)
        # the same again: two full pages come from the prefix cache, one chunk at 16
        assert eng.generate(prompt, max_tokens=2) == _reference(params, prompt, 2)
        assert eng.stats()["prefix_tokens_reused"] == 16
        assert _prefill_kv(eng) == (4, 44 + 20, 4 * 64)
        # its first two pages alone: a full hit recomputes the last token, at 15
        assert eng.generate(prompt[:16], max_tokens=2) == _reference(params, prompt[:16], 2)
        assert _prefill_kv(eng) == (5, 64 + 16, 5 * 64)
    finally:
        eng.shutdown()


def test_prefill_kv_counters_leave_out_what_a_window_hides():
    """A sliding layer's chunk visits ``start + n`` less the positions below
    its first query's window; the count is the mean over the layers."""
    cfg = TransformerConfig(
        vocab_size=89, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64, attention="dense",
        dtype=jnp.float32, layer_types=("sliding", "full"), sliding_window=8)
    assert cfg.layer_windows == (8, 0)
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(3)), max_batch_size=2, max_seq_len=64, kv_block_size=8,
                    prefill_chunk_tokens=8)
    try:
        assert len(eng.generate(list(range(1, 21)), max_tokens=2)) == 2
        # sliding: 8 - 0, 16 - 1, 20 - 9 (the first query at 16 sees from 9 on); full: 8, 16, 20
        assert _prefill_kv(eng) == (3, ((8 + 15 + 11) + (8 + 16 + 20)) / 2, 3 * 64)
        # one bucketed call a prompt (prefill_chunk_tokens 0) counts the same way
        one_shot = LLMEngine(cfg, eng.runner.params, max_batch_size=2, max_seq_len=64, kv_block_size=8)
        try:
            assert len(one_shot.generate(list(range(1, 21)), max_tokens=2)) == 2
            assert _prefill_kv(one_shot) == (1, 20, 64)
        finally:
            one_shot.shutdown()
    finally:
        eng.shutdown()
