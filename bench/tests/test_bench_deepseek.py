"""The language-model serving driver end to end at a tiny size on the
CPU, with a planted top-k renormalisation that the check must refuse; the
cost functions against hand counts at published widths; the cell's
readers on a synthetic record."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, lm_cost

TINY = {"hidden_size": 64, "vocab_size": 512, "num_attention_heads": 4,
        "kv_lora_rank": 32, "qk_rope_head_dim": 8, "qk_nope_head_dim": 16,
        "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "intermediate_size": 128}
# float32, so that the comparison sees the program's mathematics alone; at
# this width bfloat16 rounding reads 1.2e-2 to 6.1e-2 on the CPU, and the
# bfloat16 comparison at published widths is the chip's
OVERRIDES = {"dtype": "float32", "d_model": 64, "vocab_size": 512,
             "n_heads": 4, "n_kv_heads": 4, "head_dim": 24, "kv_lora_rank": 32,
             "qk_rope_dim": 8, "qk_nope_dim": 16, "v_head_dim": 16,
             "n_experts": 8, "top_k": 2, "d_expert": 32, "d_ff_dense": 128}


def _driver():
    return harness.load_module(os.path.join(harness.BENCH, "drivers",
                                            "lm_serve.py"))


def _files():
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         "deepseek-v2-lite.json"))
    mix = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         "lm-chat.json"))
    return cfg, mix


def _tiny(seconds=1.5):
    cfg, mix = _files()
    cfg = dict(cfg, **TINY, num_hidden_layers=3, overrides=OVERRIDES,
               max_batch=4, max_seq=48, pad_multiple=16)
    mix = dict(mix, rate_per_s=6.0, n_min=8, n_max=40, new_tokens=8)
    return cfg, mix


@pytest.fixture(scope="module")
def served():
    drv = _driver()
    cfg, mix = _tiny()
    state = drv.setup(cfg, mix, 2**31 + 77, 1.5)
    record = drv.window(state, 1.5, harness.Tracer(False, "", 1.5))
    return drv, state, record


def test_file_states_what_the_program_runs():
    """The configuration file's published keys are the program's, at the
    file's depth; a key that differs is refused."""
    drv = _driver()
    cfg, _ = _files()
    mc = drv.model_config(cfg)
    assert (mc.n_layers, cfg["published"]["num_hidden_layers"]) == (7, 27)
    assert cfg["reduced"] == ["num_hidden_layers"]
    with pytest.raises(ValueError, match="norm_topk_prob"):
        drv.model_config(dict(cfg, norm_topk_prob=True))


def test_window_serves_every_request_correctly(served):
    drv, state, record = served
    assert record["attempted"] == len(state["prompts"]) > 4
    assert record["failed"] == 0
    checks, correct = drv.check(state, record)
    assert correct, checks
    assert checks["moe_dropped"]["value"] == 0
    assert checks["logit_rel_err"]["value"] < checks["logit_rel_err"]["limit"]
    eng = record["engine"]
    assert eng["prefill_tokens"] == sum(len(p) for p in state["prompts"])
    assert eng["prefill_padded_tokens"] > eng["prefill_tokens"]
    # every live assignment counted: tokens x experts per token x MoE layers
    live = eng["prefill_tokens"] + eng["decode_rows_live"]
    assert sum(eng["moe_expert_tokens"]) == live * 2 * 2
    # the prefill's padding, as ``pad_overhead`` reads it
    assert (eng["payload_elems"], eng["padded_elems"]) == \
        (eng["prefill_tokens"], eng["prefill_padded_tokens"])
    assert _reader("pad_overhead").read(record, None, {}) == pytest.approx(
        eng["prefill_padded_tokens"] / eng["prefill_tokens"] - 1.0)


def test_planted_topk_renormalisation_is_not_correct(served):
    """The same requests through a program that renormalises the top-k
    gates (the published model does not) fail the check."""
    drv, state, _ = served
    from repro.serve.engine import Engine
    cfg = state["cfg"]
    bad = Engine(state["mc"].replace(norm_topk_prob=True), state["params"],
                 max_batch=cfg["max_batch"], max_seq=cfg["max_seq"])
    planted = dict(state, engine=bad)
    record = drv.window(planted, 1.5, harness.Tracer(False, "", 1.5))
    checks, correct = drv.check(planted, record)
    assert not correct
    assert checks["logit_rel_err"]["value"] > checks["logit_rel_err"]["limit"]


def test_planted_lost_group_is_not_correct(served, monkeypatch):
    """The same requests through a program whose grouped expert products
    lose the busiest expert's group (forced at every size) fail the check
    on the drop count."""
    drv, state, _ = served
    from repro.models import moe
    from repro.serve.engine import Engine
    real = moe._group_sizes

    def planted(key, e_local, n_groups, first):
        sizes = real(key, e_local, n_groups, first)
        return sizes.at[jnp.argmax(sizes)].set(0)

    monkeypatch.setattr(moe, "_group_sizes", planted)
    monkeypatch.setattr(moe, "GROUPED_TILE_ROWS", -10 ** 9)
    cfg = state["cfg"]
    bad = Engine(state["mc"], state["params"], max_batch=cfg["max_batch"],
                 max_seq=cfg["max_seq"])
    planted_state = dict(state, engine=bad)
    record = drv.window(planted_state, 1.5, harness.Tracer(False, "", 1.5))
    checks, correct = drv.check(planted_state, record)
    assert not correct
    assert checks["moe_dropped"]["value"] > 0


def test_controls_fail_the_check(served):
    drv, state, record = served
    out = drv.readings(state, record)
    limit = drv.LOGIT_LIMIT
    assert out["logit_rel_err"] < limit
    for name in ("topk_renorm", "no_mscale_sq", "reference_float8"):
        assert out[f"control.{name}.logit_rel_err"] > limit, (name, out)
    assert out["control.capacity_drops.moe_dropped"] > 0


def test_cost_functions_hand_counted_at_published_widths():
    from repro.configs import get_config
    c = get_config("deepseek-v2-lite-16b").replace(n_layers=7)
    d, v = 2048, 102_400
    attn = d * 16 * 192 + d * (512 + 64) + 512 * 16 * 256 + 16 * 128 * d
    expert = 3 * d * 1408
    # 419.4M embedding and head, 81.0M dense layer, 6 x 584.8M MoE layers
    assert 2 * v * d == 419_430_400
    assert attn + 3 * d * 10_944 == 81_002_496
    assert attn + d * 64 + 66 * expert == 584_843_264
    assert lm_cost.matrix_params(c) == 4_009_492_480
    # the program holds those plus the norm gains
    from repro.models import model as M
    import jax
    shapes = jax.eval_shape(lambda k: M.init(c, k), jax.random.PRNGKey(0))
    norms = 7 * (2 * d + 512) + d
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == \
        4_009_492_480 + norms
    # prefill: 1.5786 GFLOP a token in matrix products, plus attention
    per_token = 2 * (7 * attn + 3 * d * 10_944
                     + 6 * (d * 64 + 8 * expert) + d * v)
    assert per_token == 1_578_631_168
    assert lm_cost.prefill_flops(c, [1]) == per_token + 7 * 2 * 16 * 320
    assert lm_cost.prefill_flops(c, [3]) == 3 * per_token + 7 * 2 * 16 * 320 * 6
    # decode: absorbed attention over L + 1 latent and rope entries
    assert lm_cost.decode_flops(c, [9]) == per_token + \
        7 * 2 * 16 * (512 + 64 + 512) * 10
    # bytes: weights outside the routed experts whole, experts touched,
    # the cache prefixes read with the new entries, the entries written
    fixed = (7 * attn + 3 * d * 10_944 + 6 * 2 * expert + d * v + 2 * d) * 2 \
        + 6 * d * 64 * 4
    entry = 7 * 576 * 2
    assert lm_cost.decode_bytes(c, [10, 20], 300.0) == \
        fixed + 300 * expert * 2 + (11 + 21) * entry + 2 * entry
    # all 64 experts of 6 layers touched by 32 rows at 1,024 tokens: 7.87 GB
    full = lm_cost.decode_bytes(c, [1024] * 32, 384)
    assert full == fixed + 30 * d * 2 + 384 * expert * 2 + 32 * 1026 * entry
    assert 7.8e9 < full < 7.9e9


def _reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            name + ".py"))


def test_readers_on_a_synthetic_record():
    ctx = {"peaks": {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}}
    record = {"trace_start_s": 5.0,
              "latency_ms": np.array([10.0, 20.0, 30.0, 40.0, 50.0]),
              "queue_wait_ms": np.array([0.0, 1.0, 2.0, 3.0, 4.0]),
              "events": [(4.0, "prefill", 1e6, 0.0),
                         (5.5, "prefill", 300.0, 0.0),
                         (6.0, "decode", 100.0, 40.0),
                         (6.5, "decode", 100.0, 40.0)],
              "engine": {"moe_expert_tokens": [1, 1, 1, 5]}}
    trace = {"programs_s": {"jit_prefill": 2.0, "jit_serve_step": 2.0,
                            "jit_argmax": 9.0}}
    assert _reader("model_mfu").read(record, trace, ctx) == \
        pytest.approx(100 * 500 / (100 * 4.0))
    assert _reader("model_hbm_share").read(record, trace, ctx) == \
        pytest.approx(100 * 80 / (10 * 2.0))
    assert _reader("moe_load_max").read(record, None, ctx) == 2.5
    assert _reader("idle_share.port").read(
        record, {"busy_s": 3.0, "window_s": 4.0}, ctx) == pytest.approx(25.0)
    assert _reader("port_p95_ms").read(record, None, ctx) == \
        pytest.approx(48.0)
    assert _reader("queue_wait_p95_ms").read(record, None, ctx) == \
        pytest.approx(3.8)
    # an untraced run, or a trace without the programs, reads nothing
    assert _reader("model_mfu").read(record, None, ctx) is None
    assert _reader("model_hbm_share").read(
        record, {"programs_s": {}}, ctx) is None
    assert _reader("moe_load_max").read(
        {"engine": {"moe_expert_tokens": [0, 0]}}, None, ctx) is None


def test_run_end_to_end(monkeypatch, capsys):
    """``bench/run.py`` for the cell with its look for a chip skipped, the
    compile cache left alone and the configuration and mix at the tiny
    size: the result line carries the cell's end-to-end metrics."""
    import json
    monkeypatch.setattr(harness, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    load = harness.load_json
    cfg, mix = _tiny()

    def small(path):
        d = load(path)
        if d.get("driver") == "lm_serve":
            return cfg
        if d.get("kind") == "open_loop" and "new_tokens" in d:
            return mix
        return d

    monkeypatch.setattr(harness, "load_json", small)
    run = harness.load_module(os.path.join(harness.BENCH, "run.py"),
                              "bench_run_main")
    assert run.main(["--workload", "deepseek-v2-lite.chat", "--seed",
                     str(2**31 + 3), "--seconds", "1", "--trace", "0"]) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"port_p95_ms", "port_p50_ms", "setup_s"}
    assert list(line["checks"]) == ["logit_rel_err", "moe_dropped",
                                    "unanswered"]
