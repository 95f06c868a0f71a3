"""DeepSeek-V2-Lite on the normal serving path at a tiny size on the CPU:
the plain float32 reference (``bench/refs/deepseek_v2.py``) against
``transformers`` and DeepSeek's formulas, the program against the
reference, the dropless MoE, the published gates, per-row prompt lengths,
and ``Engine``'s spans and counters."""
import glob
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.refs import deepseek_v2 as ref
from repro.configs import get_config
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model as M
from repro.models import moe as MoE
from repro.serve.engine import Engine

PUBLISHED_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096}


def _tiny(**kw):
    """deepseek-v2-lite-16b's program at tiny widths, float32."""
    return get_config("deepseek-v2-lite-16b").replace(
        n_layers=3, d_model=64, vocab_size=512, n_heads=4, n_kv_heads=4,
        head_dim=24, kv_lora_rank=32, qk_rope_dim=8, qk_nope_dim=16,
        v_head_dim=16, n_experts=8, top_k=2, d_expert=32, d_ff_dense=128,
        dtype="float32", **kw)


def _driver():
    return harness.load_module(os.path.join(harness.BENCH, "drivers",
                                            "lm_serve.py"))


def _reference(params, cfg, tokens, at):
    return _driver().reference_logits(params, cfg, tokens, at)


# --- (a) the reference against transformers --------------------------------

def test_reference_matches_transformers():
    """Same seeded weights, rope_scaling None, norm_topk_prob false: the
    reference's logits are transformers' DeepseekV2ForCausalLM's."""
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM
    cfg = _tiny(rope_scaling=None)
    params = jax.jit(M.init, static_argnums=0)(cfg, jax.random.PRNGKey(3))
    hf = DeepseekV2Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, q_lora_rank=None, qk_rope_head_dim=8,
        qk_nope_head_dim=16, v_head_dim=16, n_routed_experts=8,
        n_shared_experts=2, num_experts_per_tok=2, moe_intermediate_size=32,
        first_k_dense_replace=1, topk_method="greedy", norm_topk_prob=False,
        routed_scaling_factor=1.0, rope_scaling=None, rms_norm_eps=1e-6,
        tie_word_embeddings=False, max_position_embeddings=64,
        attn_implementation="eager")
    model = DeepseekV2ForCausalLM(hf).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))  # noqa: E731
    sd = {"model.embed_tokens.weight": t(params["embed"]["emb"]),
          "model.norm.weight": t(params["final_norm"]["w"]),
          "lm_head.weight": t(params["embed"]["head"]).T}
    layers = _driver().reference_layers(params, cfg)
    for i, (kind, w) in enumerate(layers):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = t(w["input_norm"])
        sd[p + "post_attention_layernorm.weight"] = t(w["post_norm"])
        a = p + "self_attn."
        sd[a + "q_proj.weight"] = t(w["q_proj"]).T
        sd[a + "kv_a_proj_with_mqa.weight"] = t(w["kv_a_proj"]).T
        sd[a + "kv_a_layernorm.weight"] = t(w["kv_a_norm"])
        sd[a + "kv_b_proj.weight"] = t(w["kv_b_proj"]).T
        sd[a + "o_proj.weight"] = t(w["o_proj"]).T
        m = p + "mlp."
        if kind == "dense":
            for k, n in (("gate", "gate_proj"), ("up", "up_proj"),
                         ("down", "down_proj")):
                sd[m + n + ".weight"] = t(w[k]).T
            continue
        sd[m + "gate.weight"] = t(w["router"]).T
        for e in range(8):
            for k, n in (("experts_gate", "gate_proj"),
                         ("experts_up", "up_proj"),
                         ("experts_down", "down_proj")):
                sd[f"{m}experts.{e}.{n}.weight"] = t(w[k][e]).T
        for k, n in (("shared_gate", "gate_proj"), ("shared_up", "up_proj"),
                     ("shared_down", "down_proj")):
            sd[f"{m}shared_experts.{n}.weight"] = t(w[k]).T
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and not [k for k in missing
                                   if "rotary" not in k], missing
    toks = np.random.default_rng(0).integers(0, 512, 12)
    with torch.no_grad():
        want = model(torch.tensor(toks)[None]).logits[0].numpy()
    got = _reference(params, cfg, toks, list(range(12)))
    assert ref.rel_err(got, want) < 1e-5


# --- (b) YaRN and the softmax scale as DeepSeek writes them ----------------

def _deepseek_yarn(dim, base, s):
    """modeling_deepseek.py's DeepseekV2YarnRotaryEmbedding, in NumPy."""
    def corr(rot):
        return (dim * math.log(s["original_max_position_embeddings"] /
                               (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr(s["beta_fast"])), 0)
    high = min(math.ceil(corr(s["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = 1.0 / (s["factor"] *
                   base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) /
                   (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def test_yarn_frequencies_and_softmax_scale_at_published_scaling():
    want = _deepseek_yarn(64, 10000.0, PUBLISHED_YARN)
    np.testing.assert_allclose(ref.inv_freq(64, 10000.0, PUBLISHED_YARN),
                               want, rtol=1e-6)
    cfg = get_config("deepseek-v2-lite-16b")
    np.testing.assert_allclose(
        L.rope_frequencies(64, cfg.rope_theta, cfg.rope_scaling), want,
        rtol=1e-6)
    # the high frequencies extrapolate, the low ones interpolate by 40
    extra = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    assert want[0] == pytest.approx(extra[0])
    assert want[-1] == pytest.approx(extra[-1] / 40)
    m = 0.1 * 0.707 * math.log(40) + 1.0
    assert m == pytest.approx(1.2608, abs=1e-4)
    rc = {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
          "rope_scaling": PUBLISHED_YARN}
    assert ref.softmax_scale(rc) == pytest.approx(192 ** -0.5 * m * m)
    assert A.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    # mscale == mscale_all_dim: cos and sin are not rescaled
    assert ref.rope_mscale(PUBLISHED_YARN) == 1.0
    # without rope scaling the scale is transformers' q_head_dim ** -0.5
    assert A.mla_softmax_scale(cfg.replace(rope_scaling=None)) == \
        pytest.approx(192 ** -0.5)


# --- (c) Engine through the cache, ragged rows, against the reference ------

# the expert paths: few tokens run every expert densely; forcing the
# grouped product's tile below zero makes every size run grouped
PATHS = {"dense": None, "grouped": -10 ** 9}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_engine_ragged_prefill_and_decode_match_reference(path, monkeypatch):
    if PATHS[path] is not None:
        monkeypatch.setattr(MoE, "GROUPED_TILE_ROWS", PATHS[path])
    cfg = _tiny()
    params = jax.jit(M.init, static_argnums=0)(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    lens = np.array([5, 12, 1, 0])                  # the last row inert
    prompts = np.zeros((4, 12), np.int32)
    for r, n in enumerate(lens):
        prompts[r, :n] = rng.integers(0, 512, n)
    eng = Engine(cfg, params, max_batch=4, max_seq=20)
    first = eng.prefill(prompts, lens)
    prefill = np.asarray(eng.logits)
    steps = {}
    toks = eng.decode(first, 5, on_step=lambda i: steps.__setitem__(
        i, np.asarray(eng.logits)))
    gen = np.concatenate([np.asarray(first)[:, None], toks], 1)
    for r, n in enumerate(lens[:3]):
        seq = np.concatenate([prompts[r, :n], gen[r, :5]])
        want = _reference(params, cfg, seq, [n - 1] + [n + i for i in range(5)])
        got = [prefill[r]] + [steps[i][r] for i in range(5)]
        for g, w in zip(got, want):
            assert ref.rel_err(g[:512], w) < 1e-4
    st = eng.stats()
    assert st["prefill_tokens"] == 18 and st["prefill_padded_tokens"] == 48
    assert st["decode_steps"] == 5 and st["decode_rows_live"] == 15
    assert st["moe_dropped"] == 0
    # live tokens only: (18 + 15) tokens x 2 experts x 2 MoE layers
    assert int(st["moe_expert_tokens"].sum()) == 33 * 2 * 2


# --- (d) the dropless MoE at forced imbalance --------------------------------

def _dense_moe(p, x, cfg):
    """Each token through its own top-k experts, one at a time (float32)."""
    xf = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = xf @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    out = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        top = np.argsort(-probs[t])[:cfg.top_k]
        g = probs[t, top]
        if cfg.norm_topk_prob:
            g = g / g.sum()
        for e, w in zip(top, g):
            wg, wu, wd = (np.asarray(p[k][e], np.float64)
                          for k in ("we_g", "we_u", "we_d"))
            out[t] += w * ((silu(xf[t] @ wg) * (xf[t] @ wu)) @ wd)
        if "shared" not in p:
            continue
        sh = p["shared"]
        out[t] += (silu(xf[t] @ np.asarray(sh["wg"], np.float64)) *
                   (xf[t] @ np.asarray(sh["wu"], np.float64))) @ \
            np.asarray(sh["wd"], np.float64)
    return out.reshape(x.shape)


@pytest.mark.parametrize("path,block", [("dense", None), ("grouped", None),
                                        ("grouped", 16)])
def test_dropless_moe_at_forced_imbalance(path, block, monkeypatch):
    """Every token prefers experts 0 and 1 (64 assignments where capacity
    dispatch keeps 16 an expert): the serving modes keep them all, dense,
    grouped, in token blocks or not; training's capacity dispatch drops
    and counts."""
    if PATHS[path] is not None:
        monkeypatch.setattr(MoE, "GROUPED_TILE_ROWS", PATHS[path])
    if block:
        monkeypatch.setattr(MoE, "TOKEN_BLOCK", block)
    cfg = _tiny()
    p = MoE.moe_init(jax.random.PRNGKey(2), cfg)
    p["router"] = p["router"].at[:, :2].add(0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (2, 16, 64)))
    want = _dense_moe(p, x, cfg)
    y, _, st = MoE.moe_apply(p, x, cfg, dropless=True, stats=True)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    assert int(st["dropped"]) == 0
    counts = np.asarray(st["expert_tokens"])
    assert counts.sum() == 64 and counts[:2].sum() > 50
    y, _, st = MoE.moe_apply(p, x, cfg, stats=True)
    assert int(st["dropped"]) > 0
    assert not np.allclose(np.asarray(y), want, rtol=1e-2, atol=1e-3)


# --- (e) the published gates, and granite's unchanged -----------------------

def test_gates_unnormalised_as_published_and_granite_normalised():
    cfg = _tiny()
    p = MoE.moe_init(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (10, 64))
    gates, idx, _ = MoE._route(p, x, cfg)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates),
        np.asarray(jnp.take_along_axis(probs, idx, axis=-1)), rtol=1e-6)
    assert float(jnp.max(jnp.sum(gates, -1))) < 0.99
    g = get_config("granite-moe-1b-a400m").reduced().replace(
        dtype="float32")
    assert g.norm_topk_prob
    pg = MoE.moe_init(jax.random.PRNGKey(7), g)
    gates, _, _ = MoE._route(pg, x, g)
    np.testing.assert_allclose(np.asarray(jnp.sum(gates, -1)), 1.0,
                               rtol=1e-6)
    xb = x[None]
    y, _ = MoE.moe_apply(pg, xb, g, dropless=True)
    np.testing.assert_allclose(np.asarray(y), _dense_moe(pg, xb, g),
                               rtol=2e-4, atol=2e-5)


# --- (f) per-row lengths ------------------------------------------------------

def test_row_alone_equals_row_in_padded_batch():
    cfg = _tiny()
    params = jax.jit(M.init, static_argnums=0)(cfg, jax.random.PRNGKey(8))
    rng = np.random.default_rng(8)
    row = rng.integers(0, 512, 7).astype(np.int32)
    alone = Engine(cfg, params, max_batch=1, max_seq=16)
    a_first = alone.prefill(row[None], None)
    a_logits = np.asarray(alone.logits)
    a_toks = alone.decode(a_first, 4)
    batch = np.zeros((3, 16), np.int32)
    batch[0, :11] = rng.integers(0, 512, 11)
    batch[1, :7] = row
    together = Engine(cfg, params, max_batch=3, max_seq=24)
    t_first = together.prefill(batch, [11, 7, 0])
    np.testing.assert_allclose(np.asarray(together.logits)[1], a_logits[0],
                               rtol=1e-4, atol=1e-5)
    t_toks = together.decode(t_first, 4)
    np.testing.assert_array_equal(t_toks[1], a_toks[0])


def test_ragged_prefill_refused_for_recurrent_state():
    cfg = get_config("mamba2-1.3b").reduced().replace(dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_batch=2, max_seq=16)
    prompts = np.ones((2, 8), np.int32)
    with pytest.raises(ValueError, match="recurrent state"):
        eng.prefill(prompts, [8, 5])
    eng.prefill(prompts, [8, 8])          # equal lengths are no padding
    eng.prefill(prompts)


# --- spans, named scopes and counters ----------------------------------------

def _engine_spans(log_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    prof = ProfileData.from_file(path)
    evs = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
           for plane in prof.planes for line in plane.lines
           for ev in line.events if ev.name.startswith("engine.")]
    return sorted(evs, key=lambda ev: (ev[1], -ev[2]))


def test_engine_spans_and_counters(tmp_path):
    cfg = _tiny()
    params = jax.jit(M.init, static_argnums=0)(cfg, jax.random.PRNGKey(9))
    eng = Engine(cfg, params, max_batch=2, max_seq=16)
    prompts = np.ones((2, 8), np.int32)
    eng.decode(eng.prefill(prompts, [8, 3]), 1)      # compiled untraced
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.decode(eng.prefill(prompts, [6, 0]), 2)
    finally:
        jax.profiler.stop_trace()
    evs = _engine_spans(str(tmp_path))
    assert [e[0] for e in evs] == ["engine.prefill", "engine.decode_step",
                                   "engine.fetch", "engine.decode_step",
                                   "engine.fetch"]
    assert evs[0][3] == {"rows": 2, "width": 8, "tokens": 6}
    assert [e[3] for e in evs if e[0] == "engine.decode_step"] == \
        [{"step": 0, "rows": 1}, {"step": 1, "rows": 1}]
    assert all(a[2] <= b[1] for a, b in zip(evs, evs[1:]))
    st = eng.stats()
    assert st["prefill_tokens"] == 17 and st["prefill_padded_tokens"] == 32
    assert st["decode_steps"] == 3 and st["decode_rows_live"] == 4
    assert int(st["moe_expert_tokens"].sum()) == (17 + 4) * 2 * 2
    assert st["moe_dropped"] == 0
    assert 0 < st["decode_experts_touched"] <= 3 * 2 * 8


def test_planted_capacity_drop_counts():
    """The training dispatch in the serving modes drops at imbalance, and
    the engine's counter sees it."""
    cfg = _tiny(capacity_factor=0.25)
    params = jax.jit(M.init, static_argnums=0)(cfg, jax.random.PRNGKey(10))
    eng = Engine(cfg, params, max_batch=4, max_seq=48, dropless=False)
    eng.prefill(np.ones((4, 40), np.int32), [40, 30, 20, 10])
    assert eng.stats()["moe_dropped"] > 0


def _lose_busiest_group(monkeypatch, shift=False):
    """Plant a fault in the grouped path's group sizes: the busiest of the
    block's groups loses its rows, or (``shift``) every size lands one
    group late; the grouped path is forced at every size."""
    real = MoE._group_sizes

    def planted(key, e_local, n_groups, first):
        sizes = real(key, e_local, n_groups, first)
        if shift:
            return jnp.roll(sizes, 1)
        return sizes.at[jnp.argmax(sizes)].set(0)

    monkeypatch.setattr(MoE, "_group_sizes", planted)
    monkeypatch.setattr(MoE, "GROUPED_TILE_ROWS", PATHS["grouped"])


@pytest.mark.parametrize("shift", [False, True])
def test_lost_group_in_dropless_path_counts(shift, monkeypatch):
    """A group the grouped products do not cover is counted as dropped,
    alone and in the engine's layer scan (the stacked groups)."""
    _lose_busiest_group(monkeypatch, shift)
    cfg = _tiny()
    p = MoE.moe_init(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 64))
    _, _, st = MoE.moe_apply(p, x, cfg, dropless=True, stats=True)
    assert int(st["dropped"]) > 0
    params = jax.jit(M.init, static_argnums=0)(cfg, jax.random.PRNGKey(10))
    eng = Engine(cfg, params, max_batch=2, max_seq=24)
    eng.decode(eng.prefill(np.ones((2, 16), np.int32), [16, 9]), 2)
    assert eng.stats()["moe_dropped"] > 0


def test_layers_carry_named_scopes():
    cfg = _tiny()
    params = jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 2, 16))
    fn = lambda p, c, t: M.forward(p, cfg, {"tokens": t},  # noqa: E731
                                   mode="prefill", cache=c)[0]
    hlo = jax.jit(fn).lower(params, cache, jax.ShapeDtypeStruct(
        (2, 8), jnp.int32)).compile().as_text()
    for scope in ("mla", "moe.route", "moe.experts", "moe.shared",
                  "dense_mlp"):
        assert f"/{scope}/" in hlo, scope


def test_rope_columns_give_the_reference_the_same_rotation():
    """The program rotates half-split pairs of its rope columns; with the
    columns reordered (``rope_columns``) the reference's interleaved
    rotation gives the same query-key products at YaRN scaling."""
    cfg = _tiny()
    drv = _driver()
    h, nd, r, kvr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, \
        cfg.kv_lora_rank
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (6, 64))
    wq = jax.random.normal(ks[1], (64, h * (nd + r)))
    wkv = jax.random.normal(ks[2], (64, kvr + r))
    pos = jnp.arange(6)[None]
    q = L.rope_apply((x @ wq).reshape(1, 6, h, nd + r)[..., nd:], pos,
                     cfg.rope_theta, cfg.rope_scaling)[0]
    k = L.rope_apply((x @ wkv)[None, :, None, kvr:], pos, cfg.rope_theta,
                     cfg.rope_scaling)[0]
    want = np.einsum("qhd,khd->hqk", q, jnp.broadcast_to(k, (6, h, r)))
    wq2, wkv2 = drv.rope_columns(wq, wkv, cfg)
    rc = drv.reference_config(cfg)
    emb = np.outer(np.arange(6), ref.inv_freq(r, rc["rope_theta"],
                                              rc["rope_scaling"]))
    emb = np.concatenate([emb, emb], -1)
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    q2 = ref._apply_rope((x @ wq2).reshape(6, h, nd + r)[..., nd:], cos, sin)
    k2 = ref._apply_rope((x @ wkv2)[:, None, kvr:], cos, sin)
    got = np.einsum("qhd,khd->hqk", q2, jnp.broadcast_to(k2, (6, h, r)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    # without the reorder the rotation differs
    q3 = ref._apply_rope((x @ wq).reshape(6, h, nd + r)[..., nd:], cos, sin)
    k3 = ref._apply_rope((x @ wkv)[:, None, kvr:], cos, sin)
    bad = np.einsum("qhd,khd->hqk", q3, jnp.broadcast_to(k3, (6, h, r)))
    assert not np.allclose(bad, want, rtol=1e-2, atol=1e-1)


# --- (g) a decode step writes its new cache entries in place -----------------

CACHE_CASES = {
    "mla": _tiny,
    "gqa": lambda: get_config("mistral-large-123b").reduced().replace(
        dtype="float32"),
    # local layers keep a ring of 8 slots, which the decode steps wrap
    "gqa-ring": lambda: get_config("gemma2-2b").reduced().replace(
        dtype="float32", window=8),
}


def _stacked_leaves(cache):
    """Every cache leaf as a host array (L, B, P, ...): the scanned
    layers' stacks as they are, a lone layer's with a leading axis."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        stacked = any(getattr(p, "key", None) == "unit" for p in path)
        out.append(np.array(leaf if stacked else leaf[None]))
    return out


def _logits_reference(params, cfg, seq, at):
    if cfg.attn_kind == "mla":
        return _reference(params, cfg, seq, at)
    logits = M.forward(params, cfg, {"tokens": jnp.asarray(seq)[None]},
                       mode="train")[0]
    return np.asarray(logits[0, np.asarray(at)])


@pytest.mark.parametrize("case", sorted(CACHE_CASES))
def test_decode_writes_only_its_new_entries(case):
    """Ragged rows and an inert one through prefill and six decode steps:
    the logits are the float32 reference's; each entry a step wrote is
    what a prefill of the same tokens writes; every other entry, the
    inert row's among them, is bitwise what the prefill left; and each
    step counts every layer's write as in place."""
    cfg = CACHE_CASES[case]()
    params = jax.jit(M.init, static_argnums=0)(cfg, jax.random.PRNGKey(11))
    rng = np.random.default_rng(11)
    lens, steps = np.array([5, 8, 2, 0]), 6
    prompts = np.zeros((4, 8), np.int32)
    for r, n in enumerate(lens):
        prompts[r, :n] = rng.integers(0, cfg.vocab_size, n)
    eng = Engine(cfg, params, max_batch=4, max_seq=16)
    first = eng.prefill(prompts, lens)
    before = _stacked_leaves(eng.cache)
    logits = {}
    toks = eng.decode(first, steps, on_step=lambda i: logits.__setitem__(
        i, np.asarray(eng.logits)))
    after = _stacked_leaves(eng.cache)
    gen = np.concatenate([np.asarray(first)[:, None], toks], 1)
    written = [np.zeros(a.shape[1:3], bool) for a in after]
    for r, n in enumerate(lens):
        if n == 0:
            continue
        seq = np.concatenate([prompts[r, :n], gen[r, :steps]])
        want = _logits_reference(params, cfg, seq, [n + i
                                                    for i in range(steps)])
        for i in range(steps):
            assert ref.rel_err(logits[i][r, :cfg.vocab_size], want[i]) < 1e-4
        one = M.init_cache(cfg, 1, 16)
        _, one, _ = M.forward(params, cfg, {"tokens": jnp.asarray(seq)[None]},
                              mode="prefill", cache=one)
        for a, o, w in zip(after, _stacked_leaves(one), written):
            slots = (n + np.arange(steps)) % a.shape[2]
            w[r, slots] = True
            np.testing.assert_allclose(a[:, r, slots], o[:, 0, slots],
                                       rtol=1e-4, atol=1e-5)
    for a, b, w in zip(after, before, written):
        assert w.any()
        np.testing.assert_array_equal(a[:, ~w], b[:, ~w])
    st = eng.stats()
    assert st["decode_cache_writes_in_place"] == steps * cfg.n_layers


def test_recurrent_state_counts_no_in_place_writes():
    cfg = get_config("mamba2-1.3b").reduced().replace(dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_batch=2, max_seq=16)
    eng.decode(eng.prefill(np.ones((2, 8), np.int32)), 3)
    st = eng.stats()
    assert st["decode_steps"] == 3 and st["decode_cache_writes_in_place"] == 0
