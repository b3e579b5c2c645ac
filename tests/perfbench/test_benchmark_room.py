"""What PR 28 added to the benchmark, on the CPU: the cell above the knee
(its schedule, its verdict, its metric), the logits check over prompts of
several prefill chunks, and the proof that a later PR adds a family, a
configuration, a traffic mix and a cell as new files and entries only."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, serving, stratify, system, yardstick  # noqa: E402
from benchmark.kinds import open_loop_requests  # noqa: E402

MANIFEST = manifest.load(os.path.join(ROOT, "BENCHMARK.json"))
SECONDS = MANIFEST["run_seconds"]
SATURATED = "smollm2-1.7b-serve.chat-saturated"
GOOD = {"paged runner": {"ok": True}, "served path": {"ok": True}}


def _traffic(name):
    with open(manifest.traffic_file(name, ROOT)) as f:
        return json.load(f)


def _turn(due, first, gap, n, max_tokens=None, scored=True):
    t = serving.Turn(due, [1, 2, 3], max_tokens or n, scored)
    t.sent = due + 0.001
    t.token_times = [due + first + gap * i for i in range(n)]
    t.tokens = [5] * n
    return t


# ---------------------------------------------------------------------------
# the cell above the knee: its traffic
# ---------------------------------------------------------------------------
def test_the_saturated_cell_offers_chat_steadys_mix_above_its_rate():
    hot, steady = _traffic("chat-saturated"), _traffic("chat-steady")
    for key in ("kind", "prompt_tokens", "output_tokens", "prompts", "ramp_s", "trace_s", "first_token_timeout_s"):
        assert hot[key] == steady[key], key
    assert hot["backlog"] == "expected" and "backlog" not in steady
    assert hot["rate"] > 1.3 * steady["rate"], "1.3 x a knee that 0.8 requests/s lies under"


@pytest.mark.parametrize("schedule_seed", [0, 1, 2])
def test_the_saturated_schedule_holds_the_fixed_count_in_blocks_of_eight(schedule_seed, monkeypatch):
    p = _traffic("chat-saturated")
    monkeypatch.setattr(stratify, "SCHEDULE_SEED", schedule_seed)
    plan = open_loop_requests.schedule(p, SECONDS)
    scored = [r for r in plan if r["scored"]]
    assert len(scored) == round(p["rate"] * SECONDS)
    assert len(plan) - len(scored) == round(p["rate"] * p["ramp_s"])
    per_block = Counter(int(r["due"] // (stratify.BLOCK / p["rate"])) for r in scored)
    assert all(per_block[k] == stratify.BLOCK for k in range(len(scored) // stratify.BLOCK))
    assert sorted(r["prompt_len"] for r in scored) == stratify.stratified_sizes(p["prompt_tokens"], len(scored))
    assert sorted(r["max_tokens"] for r in scored) == stratify.stratified_sizes(p["output_tokens"], len(scored))


def test_the_saturated_cell_reports_no_first_token_metric():
    """The queue grows, so a first token's wait measures the window; the gaps
    between tokens do not depend on the queue, and their tail is reported."""
    names = {x["name"] for g in ("end_to_end", "per_layer") for x in manifest.metrics_of(MANIFEST, g, SATURATED)}
    assert {"output_tokens_per_s", "itl_mean_ms", "itl_p99_ms", "setup_s", "kv_pool_in_use_share", "backlog_at_close",
            "batch_occupancy", "decode_step_dev_ms", "decode_kernel_time_share"} <= names
    assert not {n for n in names if n.startswith(("ttft", "first16"))}
    bound = next(x["bound"] for x in MANIFEST["end_to_end"] if x["name"] == "output_tokens_per_s")
    assert bound <= 0.05


# ---------------------------------------------------------------------------
# its metric and its verdict, from made-up client records
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("stamps,want", [
    ([[10.0, 20.0]], 2 / 10),                      # the window's edges are inside it
    ([[9.999, 10.001, 20.001]], 1 / 10),           # before the opening and after the close are not
    ([[1.0, 2.0], [30.0]], None),                  # nothing inside: no number, never 0
    ([[12.0, 13.0, 14.0], [15.0, 25.0]], 4 / 10),  # pooled over requests
])
def test_output_tokens_per_s_counts_the_stamps_inside_the_window(stamps, want):
    turns = []
    for times in stamps:
        t = serving.Turn(0.0, [1], 64, True)
        t.sent, t.token_times, t.tokens = 0.0, list(times), [5] * len(times)
        turns.append(t)
    got = serving.serve_metrics(turns, (10.0, 20.0))["output_tokens_per_s"]
    assert got == (None if want is None else pytest.approx(want))


def test_output_tokens_per_s_counts_the_ramps_tokens_that_arrive_in_the_window():
    ramp = _turn(-5.0, 1.0, 1.0, 30, scored=False)  # stamps -4 .. 25: 0 .. 10 lie inside (0, 10)
    scored = _turn(2.0, 1.0, 1.0, 20)               # stamps 3 .. 22: 3 .. 10 inside
    m = serving.serve_metrics([ramp, scored], (0.0, 10.0))
    assert m["output_tokens_per_s"] == pytest.approx((11 + 8) / 10.0)
    assert yardstick.count_in(ramp.token_times, (0.0, 10.0)) == 11


def _overloaded():
    """Five scored requests at a close of 50: two done or streaming, one cut
    after its first 16, one admitted just before the close, two waiting."""
    done = _turn(10.0, 2.0, 0.1, 40)
    cut = _turn(30.0, 15.0, 0.1, 20, max_tokens=64)
    cut.cancelled = True
    just = _turn(40.0, 9.9, 0.1, 16, max_tokens=64)  # first token at 49.9
    just.cancelled = True
    late = _turn(45.0, 6.0, 0.1, 16, max_tokens=64)  # first token at 51.0: after the close
    late.cancelled = True
    never = serving.Turn(48.0, [1, 2, 3], 64, True)
    never.sent, never.cancelled = 48.001, True
    ramp = _turn(-3.0, 1.0, 0.1, 30, scored=False)
    return [done, cut, just, late, never, ramp]


def test_judge_above_the_knee_leaves_the_waiting_out():
    turns = _overloaded()
    v = serving.judge(turns, 100, GOOD, close=50.0)
    assert (v["attempted"], v["failed"], v["waiting"], v["reasons"]) == (3, 0, 2, [])


def test_judge_without_the_key_is_as_it_was():
    turns = _overloaded()
    v = serving.judge(turns, 100, GOOD)
    assert v["attempted"] == 5 and v["waiting"] == 0
    assert v["failed"] == 1 and "no first 16 tokens" in v["reasons"][0]  # ``never``; ``late`` has its 16


@pytest.mark.parametrize("spoil,why", [
    (lambda ts: setattr(ts[4], "error", "Shed: queue full"), "Shed"),                  # refused while waiting: an answer
    (lambda ts: setattr(ts[0], "error", "RuntimeError: engine"), "RuntimeError"),
    (lambda ts: setattr(ts[1], "cancelled", False), "failed"),                         # ended short, nobody cancelled it
    (lambda ts: (setattr(ts[2], "token_times", ts[2].token_times[:9]),
                 setattr(ts[2], "tokens", ts[2].tokens[:9])), "no first 16 tokens"),   # admitted, then starved
    (lambda ts: setattr(ts[0], "tokens", [100] * 40), "outside the vocabulary"),
])
def test_judge_above_the_knee_still_fails_what_the_system_got_wrong(spoil, why):
    turns = _overloaded()
    spoil(turns)
    v = serving.judge(turns, 100, GOOD, close=50.0)
    assert v["reasons"] and why in " ".join(v["reasons"])
    assert v["attempted"] + v["waiting"] == 5


def test_the_backlog_key_is_expected_or_absent():
    assert serving.backlog_close({}, (0.0, 51.0)) is None
    assert serving.backlog_close({"backlog": "expected"}, (0.0, 51.0)) == 51.0
    with pytest.raises(ValueError):
        serving.backlog_close({"backlog": "tolerated"}, (0.0, 51.0))


def _queue(n_answered, n_waiting, close=50.0):
    """Requests due one a second; the first ``n_answered`` have a first token by the close."""
    turns = []
    for i in range(n_answered + n_waiting):
        if i < n_answered:
            t = _turn(float(i), 1.0, 0.1, 16, max_tokens=64)
        else:
            t = serving.Turn(float(i), [1, 2, 3], 64, True)
            t.sent = i + 0.001
        t.cancelled = True
        turns.append(t)
    return turns


def _swap(turns, i, j):
    """Request i gets what request j got from the engine, and j what i got."""
    for key in ("token_times", "tokens"):
        a, b = getattr(turns[i], key), getattr(turns[j], key)
        setattr(turns[i], key, b)
        setattr(turns[j], key, a)


@pytest.mark.parametrize("spoil,want", [
    (lambda ts: None, 0),                                    # first come, first served
    (lambda ts: _swap(ts, 19, 20), 1),                       # two neighbours changed places at the edge
    (lambda ts: _swap(ts, 5, 25), 15),                       # one request lost early: everything after it overtook it
    (lambda ts: [_swap(ts, i, j) for i, j in ((3, 21), (7, 24), (11, 27))], 17),  # the long prompts starved, later ones let through
    (lambda ts: setattr(ts[5], "error", "Shed: queue full"), 0),  # an error is an answer: it fails, it does not wait
])
def test_overtaken_counts_the_requests_answered_past_one_that_still_waits(spoil, want):
    turns = _queue(20, 10)
    spoil(turns)
    assert serving.overtaken(turns, 50.0) == want
    v = serving.judge(turns, 100, GOOD, close=50.0)
    assert v["overtaken"] == want and v["waiting"] == 10
    assert any("order of arrival" in r for r in v["reasons"]) == (want > serving.OVERTAKEN_LIMIT)


def test_the_order_is_judged_on_the_ramps_requests_too_and_only_above_the_knee():
    turns = _queue(20, 10)
    for t in turns[:8]:
        t.scored = False
    _swap(turns, 2, 25)  # a ramp request never answered
    assert serving.judge(turns, 100, GOOD, close=50.0)["overtaken"] == 18
    assert "overtaken" not in serving.judge(turns, 100, GOOD)
    assert serving.OVERTAKEN_LIMIT <= 2


def test_after_the_close_the_run_waits_for_the_judged_requests_only():
    turns = _overloaded()
    turns[3].token_times, turns[3].tokens = turns[3].token_times[:3], turns[3].tokens[:3]  # ``late``: 3 tokens so far
    t0 = serving.now()
    serving.wait_for_first_tokens(turns, 5.0, close=50.0)
    assert serving.now() - t0 < 1.0, "the two that had no first token by the close are not waited for"
    t0 = serving.now()
    serving.wait_for_first_tokens(turns, 0.2)
    assert serving.now() - t0 >= 0.2, "without a close every scored request is"


@pytest.mark.parametrize("reader,stats,want", [
    ("kv_pool_in_use_share", [{"kv_blocks_in_use": 1000, "kv_block_pool_size": 1536},
                              {"kv_blocks_in_use": 1536, "kv_block_pool_size": 1536}], (1000 / 1536 + 1.0) / 2),
    ("backlog_at_close", [{"queued": 41, "prefilling": 1}], 42),
])
def test_the_two_new_readers(reader, stats, want):
    from benchmark import run as runner

    class Sampler:
        samples = [(-1.0, {"kv_blocks_in_use": 0, "kv_block_pool_size": 1536})] + [
            (1.0 + i, s) for i, s in enumerate(stats)]

    class Probe:
        sampler = Sampler()
        stats_open = (0.0, {})
        stats_close = (10.0, stats[-1])

    read = runner.load_reader(reader, MANIFEST["paths"])
    assert read({"probe": Probe(), "window": (0.0, 10.0)}) == pytest.approx(want)


# ---------------------------------------------------------------------------
# the served-path check's prompt lengths
# ---------------------------------------------------------------------------
def test_served_prompt_tokens_are_one_two_and_three_chunks():
    run = system.load_json("benchmark/configs/smollm2-1.7b-serve.json")["run"]
    assert open_loop_requests.served_check_prompt_tokens(run) == [128, 682, 1280], "the accepted cells keep their check"


@pytest.mark.parametrize("max_prompt,longest", [(384, 5120), (5120, 5120), (6000, 6000)])
def test_the_longest_served_prompt_reaches_the_logits_checks_longest(max_prompt, longest):
    """A family whose layers differ only past some context length states one
    length, ``max_prompt``, and both checks reach it."""
    run = {"prefill_chunk_tokens": 2048, "correctness": {"max_prompt": max_prompt}}
    assert open_loop_requests.served_check_prompt_tokens(run) == [512, 2730, longest]


# ---------------------------------------------------------------------------
# the logits check over prompts of several prefill chunks
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_paged():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import init_params

    config = system.shrink_for_rehearsal(system.load_json("benchmark/configs/smollm2-1.7b-serve.json"))
    cfg = system.model_module(config).program_config(
        config, max_seq_len=config["run"]["max_seq_len"], dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    return config, cfg, init_params(cfg, jax.random.key(3))


def _with_max_prompt(config, max_prompt):
    run = dict(config["run"], correctness=dict(config["run"]["correctness"], max_prompt=max_prompt))
    return dict(config, run=run)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_within_one_chunk_the_logits_check_makes_the_calls_it_always_made(tiny_paged, seed, monkeypatch):
    """PR 24's prompts, shapes and draws: one chunk a prompt at positions
    0..C-1, a block table of one chunk and the decoded tokens, the same
    lengths from the seed."""
    import numpy as np

    from ray_tpu.models import generation

    config, cfg, params = tiny_paged
    run, cc = config["run"], config["run"]["correctness"]
    C, bs, n, k = run["prefill_chunk_tokens"], run["kv_block_size"], cc["prompts"], cc["decode_steps"]
    assert cc["max_prompt"] <= C
    calls = []
    real = generation.paged_forward_with_cache

    def spy(cfg_, params_, cache, bt, toks, positions, **kw):
        calls.append((toks.shape, bt.shape))
        return real(cfg_, params_, cache, bt, toks, positions, **kw)

    monkeypatch.setattr(generation, "paged_forward_with_cache", spy)
    seen = []
    make = system.model_module(config).make_reference

    def recording(c):
        ref_logits, ref_loss = make(c)

        def logits(params_, seq, positions=None):
            seen.append((int(seq.shape[0]), np.asarray(positions).tolist()))
            return ref_logits(params_, seq, positions)

        return logits, ref_loss

    monkeypatch.setattr(system.model_module(config), "make_reference", recording)
    got = serving.check_paged_against_reference(cfg, params, config, seed)
    assert got["ok"] and got["vectors"] == n * (1 + k)
    prefills = [c for c in calls if c[0][1] > 1]
    assert len(prefills) == 1 and prefills[0][0] == (1, C) and prefills[0][1] == (1, -(-(C + k) // bs))
    lens = np.random.default_rng([seed, 7]).integers(max(2, cc["max_prompt"] // 4), cc["max_prompt"] + 1, size=n)
    assert seen == [(cc["max_prompt"] + k, list(range(L - 1, L + k))) for L in lens.tolist()]


def test_the_logits_check_prefills_a_long_prompt_chunk_by_chunk_as_the_engine_does(tiny_paged, monkeypatch):
    from ray_tpu.models import generation

    config, cfg, params = tiny_paged
    C = config["run"]["prefill_chunk_tokens"]
    long = _with_max_prompt(config, 2 * C + C // 2)
    starts = []
    real = generation.paged_forward_with_cache

    def spy(cfg_, params_, cache, bt, toks, positions, **kw):
        if toks.shape[1] > 1:
            assert toks.shape == (1, C) and bt.shape[1] * config["run"]["kv_block_size"] >= 3 * C + 2
            starts.append(positions)
        return real(cfg_, params_, cache, bt, toks, positions, **kw)

    monkeypatch.setattr(generation, "paged_forward_with_cache", spy)
    good = serving.check_paged_against_reference(cfg, params, long, 3)
    assert good["ok"] and good["rel_err"] < 0.02, good
    assert len(starts) == 1, "one prefill program, with a traced start, for a prompt's first chunk and its later ones"
    wrong = dict(long, rope_theta=10000.0)  # the reference of another model
    assert not serving.check_paged_against_reference(cfg, params, wrong, 3)["ok"]


def test_the_longest_checked_prompt_is_max_prompt_long_when_it_spans_chunks(tiny_paged, monkeypatch):
    """So that a family whose layers differ only past some context length is
    always checked past it."""
    import numpy as np

    config, cfg, params = tiny_paged
    C = config["run"]["prefill_chunk_tokens"]
    seen = []
    make = system.model_module(config).make_reference

    def recording(c):
        ref_logits, ref_loss = make(c)

        def logits(params_, seq, positions=None):
            seen.append(int(np.asarray(positions)[0]) + 1)
            return ref_logits(params_, seq, positions)

        return logits, ref_loss

    monkeypatch.setattr(system.model_module(config), "make_reference", recording)
    serving.check_paged_against_reference(cfg, params, _with_max_prompt(config, 2 * C + C // 2), 5)
    assert max(seen) == 2 * C + C // 2 and len(seen) == config["run"]["correctness"]["prompts"]


# ---------------------------------------------------------------------------
# what the window served, held to the reference once it has closed
# ---------------------------------------------------------------------------
class _ReferenceServed:
    """Stands where ``serving.Served`` does and answers greedily from the
    reference itself; ``spoil`` alters a token where it is produced."""

    def __init__(self, config, cfg, params, spoil=None):
        self.config, self.run, self.cfg, self.params, self.spoil = config, config["run"], cfg, params, spoil
        self.ref = system.model_module(config).make_reference(config)[0]
        self.correctness = {"paged runner": {"ok": True}}

    def check_served(self, conversations):
        self.correctness["served path"] = {"ok": True}

    def stream(self, turn, stop, traced=False):
        import jax.numpy as jnp
        import numpy as np

        turn.sent = serving.now()
        seen = list(turn.prompt)
        for i in range(turn.max_tokens):
            padded = np.zeros(128, np.int32)  # one shape: the reference is causal
            padded[: len(seen)] = seen
            lg = np.asarray(self.ref(self.params, jnp.asarray(padded), jnp.asarray([len(seen) - 1])))[0]
            tok = int(np.argmax(lg))
            if self.spoil is not None and self.spoil(turn, i):
                tok = int(np.argsort(lg)[len(lg) // 2])  # a token from the middle of the field
            turn.tokens.append(tok)
            turn.token_times.append(serving.now())
            seen.append(tok)


@pytest.fixture(scope="module")
def tiny_f32():
    import jax
    import jax.numpy as jnp

    config = system.shrink_for_rehearsal(system.load_json("benchmark/configs/smollm2-1.7b-serve.json"))
    cfg = system.model_module(config).program_config(
        config, max_seq_len=config["run"]["max_seq_len"], dtype=jnp.float32, param_dtype=jnp.float32)
    params = jax.block_until_ready(system.make_params(cfg, 3, config["run"]["weights"]["embed_table_scale"]))
    return config, cfg, params


def _served_turns(served, lengths, vocab):
    import numpy as np

    rng = np.random.default_rng(8)
    turns = []
    for i, (n_prompt, n_out) in enumerate(lengths):
        t = serving.Turn(float(i), rng.integers(1, vocab, size=n_prompt).tolist(), n_out, i > 0)
        served.stream(t, None)
        turns.append(t)
    return turns


LENGTHS = [(20, 3), (60, 6), (12, 4), (33, 2), (40, 5), (9, 3)]


def test_the_windows_finished_requests_pass_where_the_reference_served_them(tiny_f32):
    config, cfg, params = tiny_f32
    served = _ReferenceServed(config, cfg, params)
    turns = _served_turns(served, LENGTHS, cfg.vocab_size)
    turns[2].tokens = turns[2].tokens[:2]  # cut short: not finished, never sampled
    got = serving.check_window_against_reference(served, turns, (0.0, serving.now()), 2**31 + 5)
    assert got["ok"] and got["worst_deficit_sd"] == 0.0
    assert (got["requests"], got["finished"], got["longest"]) == (serving.WINDOW_SAMPLE, 5, 66)
    assert got["tokens"] >= 6 + 3 * 2, "the longest request is always in the sample"


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_a_token_altered_in_the_longest_reply_fails_the_window(tiny_f32, seed):
    config, cfg, params = tiny_f32
    served = _ReferenceServed(config, cfg, params, spoil=lambda turn, i: turn.prompt_len == 60 and i == 4)
    turns = _served_turns(served, LENGTHS, cfg.vocab_size)
    got = serving.check_window_against_reference(served, turns, (0.0, serving.now()), seed)
    assert not got["ok"] and got["worst_deficit_sd"] > 1.0 and got["not_top1"] >= 1, got


def test_a_window_that_finished_nothing_is_not_correct(tiny_f32):
    config, cfg, params = tiny_f32
    served = _ReferenceServed(config, cfg, params)
    turns = _served_turns(served, LENGTHS[:2], cfg.vocab_size)
    opening = serving.now() + 1.0  # both ended before the window opened
    got = serving.check_window_against_reference(served, turns, (opening, opening + 1.0), 1)
    assert not got["ok"] and "no request finished" in got["why"]
    v = serving.judge(turns, cfg.vocab_size, {"window sample": got})
    assert any("window sample" in r for r in v["reasons"])


@pytest.mark.parametrize("spoil,correct", [
    (None, True),
    (lambda turn, i: i == 1, False),  # every reply's second token altered where it is produced
])
def test_a_driven_window_is_correct_only_if_its_served_tokens_are_the_references(tiny_f32, spoil, correct):
    """The rest of a run without the look for a chip: ``open_loop_requests.drive``
    over the harness's own generator, judge and window check, on a system that
    serves what the reference says, sound or broken underneath."""
    from benchmark import run as runner

    config, cfg, params = tiny_f32
    served = _ReferenceServed(config, cfg, params, spoil)
    p = dict(_traffic("chat-saturated"), **_traffic("chat-saturated")["rehearsal"])
    p.update(rate=4.0, ramp_s=0.5, first_token_timeout_s=60)
    p["output_tokens"] = dict(p["output_tokens"], median=3, lo=2, hi=4)
    ctx = runner.Ctx(cell={"chips": 1}, config=config, traffic=p, seed=2**31 + 9, seconds=1.5, trace=False,
                     rehearsal=True)
    out = open_loop_requests.drive(ctx, served, p, 1.5)
    assert (not out["reasons"]) == correct, out["reasons"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["compared"]) >= {"failed_requests", "overtaken_requests", "window_worst_deficit_sd"}
    value, limit = out["compared"]["window_worst_deficit_sd"]
    assert (value <= limit) == correct


# ---------------------------------------------------------------------------
# a later PR's family, configuration, traffic mix and cell: new files and entries only
# ---------------------------------------------------------------------------
NEW_FAMILY = '''"""A second family, as a later PR adds it: one new file."""


def program_config(c, **overrides):
    return {"family": "otherfam", "layers": c["num_hidden_layers"], **overrides}


def make_reference(c):
    return (lambda params, tokens, positions=None: None), (lambda params, tokens: 0.0)


def train_flops_per_token(c, seq_len):
    return 6.0 * c["hidden_size"]
'''


@pytest.fixture(scope="module")
def grown_checkout(tmp_path_factory):
    """A checkout as a later ``model_config`` PR leaves it: every file that is
    there untouched, four files added, ``BENCHMARK.json`` with entries added
    and the new cell's name appended to the metrics it reports."""
    root = tmp_path_factory.mktemp("grown")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    def files():
        return {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read() for dp, _, fs in os.walk(root) for p in fs}

    before = files()
    (root / "benchmark" / "models" / "otherfam.py").write_text(NEW_FAMILY)
    config = {"model": "otherfam", "name": "otherfam-5l-serve", "source": "https://example.org/otherfam/config.json",
              "hidden_size": 2048, "num_hidden_layers": 5, "reduced": ["num_hidden_layers"],
              "run": {"role": "serve", "prefill_chunk_tokens": 2048, "max_seq_len": 16384,
                      "correctness": {"max_prompt": 6000}},
              "rehearsal": {"config": {"hidden_size": 64}, "run": {"prefill_chunk_tokens": 32}}}
    (root / "benchmark" / "configs" / "otherfam-5l-serve.json").write_text(json.dumps(config))
    traffic = dict(_traffic("chat-steady"), rate=0.5)
    (root / "benchmark" / "traffic" / "long-chat.json").write_text(json.dumps(traffic))
    (root / "benchmark" / "layer_metrics" / "window_layers_share.py").write_text("def read(run):\n    return None\n")
    m = json.loads(json.dumps(MANIFEST))
    cell = "otherfam-5l-serve.long-chat"
    m["configs"].append({"name": "otherfam-5l-serve", "source": config["source"], "reduced": ["num_hidden_layers"],
                         "file": "benchmark/configs/otherfam-5l-serve.json", "why": "a second family"})
    m["workloads"].append({"name": cell, "config": "otherfam-5l-serve", "traffic": "long-chat", "chips": 1,
                           "why": "prompts to 12k tokens across the window's edge"})
    reported = {x["name"] for g in ("end_to_end", "per_layer")
                for x in manifest.metrics_of(MANIFEST, g, "smollm2-1.7b-serve.chat-steady")}
    for group in ("end_to_end", "per_layer"):
        for x in m[group]:
            if x["name"] in reported and "workloads" in x:
                x["workloads"].append(cell)
    m["per_layer"].append({"name": "window_layers_share", "unit": "%", "better": "lower", "source": "device_trace",
                           "layer": "kernels, serving", "moves": "itl_mean_ms", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    after = files()
    assert all(after[p] == content for p, content in before.items()), "no file that was there is edited"
    return root, m, cell


def test_a_manifest_with_one_more_family_configuration_mix_and_cell_is_sound(grown_checkout):
    root, m, cell = grown_checkout
    assert manifest.problems(m, str(root)) == []
    assert len(m["configs"]) == len(MANIFEST["configs"]) + 1 and len(m["workloads"]) == len(MANIFEST["workloads"]) + 1
    assert [x["name"] for x in manifest.metrics_of(m, "end_to_end", cell)] == [
        x["name"] for x in manifest.metrics_of(MANIFEST, "end_to_end", "smollm2-1.7b-serve.chat-steady")]


def test_the_harness_loads_the_new_cell_and_its_family_from_the_grown_checkout(grown_checkout):
    """In a process of its own, so that ``benchmark`` is the grown copy."""
    root, m, cell = grown_checkout
    walk = (
        "import json, sys; sys.path.insert(0, '.')\n"
        "from benchmark import manifest, run, system\n"
        "from benchmark.kinds import open_loop_requests\n"
        "m = manifest.load(); manifest.validate(m, run.ROOT)\n"
        f"cell, config, traffic = run.load_cell(m, {cell!r}, False)\n"
        f"_, small, _ = run.load_cell(m, {cell!r}, True)\n"
        "model = system.model_module(config)\n"
        "print(json.dumps({'root': run.ROOT, 'model': model.__name__, 'program': model.program_config(config, dtype='bfloat16'),\n"
        "  'kind': traffic['kind'], 'rate': traffic['rate'], 'chunk': small['run']['prefill_chunk_tokens'],\n"
        "  'served': open_loop_requests.served_check_prompt_tokens(config['run']),\n"
        "  'readers': [x['name'] for x in manifest.metrics_of(m, 'per_layer', cell['name'])\n"
        "              if run.load_reader(x['name'], m['paths']) is not None]}))\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "-c", walk], cwd=str(root), env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert os.path.realpath(got["root"]) == os.path.realpath(str(root))
    assert got["model"] == "benchmark.models.otherfam" and got["program"]["layers"] == 5
    assert (got["kind"], got["rate"], got["chunk"]) == ("open_loop_requests", 0.5, 32)
    assert got["served"] == [512, 2730, 6000]
    assert "window_layers_share" in got["readers"] and "decode_step_dev_ms" in got["readers"]


def test_no_test_here_names_the_whole_set_of_configurations_or_cells():
    """A later PR's entry must not break a test it may not edit."""
    here = os.path.dirname(os.path.abspath(__file__))
    for name in os.listdir(here):
        if name.endswith(".py"):
            text = open(os.path.join(here, name)).read()
            for whole_set in ('MANIFEST["configs"]} ' + '==', 'MANIFEST["workloads"]} ' + '==', 'four ' + 'in ('):
                assert whole_set not in text, (name, whole_set)


# ---------------------------------------------------------------------------
# the command, at toy sizes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_walks_the_saturated_cell(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", SATURATED,
                        "--seed", str(2**31 + 17), "--seconds", "2", "--trace", trace, "--rehearsal"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal_host_only"] is True and line["correct"] is True, p.stderr[-2000:]
    assert "metrics" not in line and line["attempted"] > 0 and line["failed"] == 0
    want = {"output_tokens_per_s", "itl_mean_ms", "itl_p99_ms", "setup_s"} if trace == "0" else {"kv_pool_in_use_share", "batch_occupancy"}
    assert want <= set(line["metric_names"]), line
    assert list(line)[-1] == "compared", "each number that decided `correct`, beside its limit, comes last"
    assert {"failed_requests", "overtaken_requests", "paged_rel_err", "served_worst_deficit_sd",
            "window_worst_deficit_sd"} <= set(line["compared"])
    assert all(set(x) == {"value", "limit"} for x in line["compared"].values())
    assert "compared " in p.stderr.strip().splitlines()[-1]
