"""Cost-driven, target-aware lowering selection (the tentpole feature):
Target registry, selection cache, VLA width rule, policy cap, explain().

These tests only exercise selection/cost paths (select/explain/isa
dispatch) — pallas kernel *execution* is covered elsewhere and needs TPU
or interpret mode.
"""
import functools
import json
import logging
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isa, targets, trace, use_policy, use_target
from repro.core.registry import REGISTRY, Lowering, explain
from repro.kernels import figure2
from repro.kernels import ops  # noqa: F401  (registers kernel lowerings)

with open(os.path.join(os.path.dirname(__file__), "golden",
                       "figure2_counts.json")) as _f:
    FIGURE2_GOLDEN = json.load(_f)


# ---------------------------------------------------------------------------
# Target registry
# ---------------------------------------------------------------------------

def test_target_registry_families():
    v5e = targets.get_target("tpu-v5e")
    assert not v5e.vla and v5e.has_mxu and v5e.has_vector_libm
    for name in targets.RVV_FAMILY:
        t = targets.get_target(name)
        assert t.vla and not t.has_mxu and not t.has_vector_libm
        assert t.vreg_elems(jnp.float32) == t.vlen // 32
        assert t.vreg_elems(jnp.int8) == t.vlen // 8
    with pytest.raises(KeyError):
        targets.get_target("no-such-target")


def test_device_kind_table():
    """A device kind maps to its Target; an unknown kind is an error,
    never a fallback to the default target."""
    assert targets.device_target("TPU v5 lite").name == "tpu-v5e"
    assert targets.device_target("TPU v6 lite").name == "tpu-v6"
    with pytest.raises(KeyError, match="cpu"):
        targets.device_target("cpu")


def test_vla_width_rule():
    """Table 2: a fixed-width register maps iff vlen >= width."""
    rvv64 = targets.get_target("rvv-64")
    rvv128 = targets.get_target("rvv-128")
    assert rvv64.supports_width(64) and not rvv64.supports_width(128)
    assert rvv128.supports_width(128)
    assert targets.get_target("tpu-v5e").supports_width(128)


def test_use_target_scoping():
    base = targets.current_target().name
    with use_target("rvv-256"):
        assert targets.current_target().name == "rvv-256"
        with use_target("tpu-v6"):
            assert targets.current_target().name == "tpu-v6"
        assert targets.current_target().name == "rvv-256"
    assert targets.current_target().name == base


def test_compile_target_is_physical():
    with use_target("rvv-128"):
        assert targets.compile_target().kind == "tpu"
    with use_target("tpu-v6"):
        assert targets.compile_target().name == "tpu-v6"


# ---------------------------------------------------------------------------
# Cost-driven selection
# ---------------------------------------------------------------------------

def test_selection_is_cost_driven():
    """The cheapest valid lowering wins; tier rank is only a tie-break."""
    x = jnp.zeros((1024, 1024), jnp.float32)
    rep = explain("vtanh", x, policy="pallas", target="rvv-128")
    costs = {c["tier"]: c["cost"] for c in rep["candidates"] if c["valid"]}
    assert rep["chosen"] == "pallas"
    assert costs["pallas"] == min(costs.values())
    # the scalarized baseline: 30 scalar-libm instrs per element
    assert costs["vector"] == trace.PRIM_SCALAR_COST["tanh"] * x.size


def test_simple_arith_keeps_vector_everywhere():
    """Paper Listing 8: no customized lowering beats one vector op."""
    a = jnp.zeros(256, jnp.float32)
    for name in targets.RVV_FAMILY + ("tpu-v5e", "tpu-v6"):
        rep = explain("vadd", a, a, policy="pallas", target=name)
        assert rep["chosen"] == "vector", (name, rep)


def test_target_sweep_flips_selection_at_small_vlen():
    """The Table-2 'x' entries: at vlen=64 a 128-bit logical register
    cannot map, so vector/customized tiers fall away and the selector
    lands on the scalar loop; at vlen>=128 the customized conversion
    wins.  This is the selection flip the static tier ladder could not
    express."""
    q = jnp.zeros(16, jnp.uint8)           # int8x16_t: 128-bit Q register
    assert REGISTRY.select("vrbit", q, policy="pallas",
                           target="rvv-64").tier == "generic"
    assert REGISTRY.select("vrbit", q, policy="pallas",
                           target="rvv-128").tier == "pallas"
    d = jnp.zeros(8, jnp.uint8)            # int8x8_t: 64-bit D register
    assert REGISTRY.select("vrbit", d, policy="pallas",
                           target="rvv-64").tier == "pallas"


def test_policy_cap_reproduces_original_simde():
    """use_policy('vector') caps the candidate set — never a customized
    lowering, matching the original-SIMDe baseline column."""
    x = jnp.zeros((512, 512), jnp.float32)
    for opname, args in [("vtanh", (x,)), ("vrelu", (x, 0.0, 6.0)),
                         ("vsqrt", (jnp.abs(x) + 1.0,))]:
        with use_target("rvv-128"):
            with use_policy("vector"):
                low = REGISTRY.select(opname, *args)
            assert low.tier in ("generic", "vector")
            full = REGISTRY.select(opname, *args, policy="pallas")
            assert full.tier == "pallas"


def test_selection_cache_hits():
    x = jnp.zeros((64, 64), jnp.float32)
    REGISTRY.cache_clear()
    a = REGISTRY.select("vtanh", x, policy="pallas", target="rvv-128")
    info1 = REGISTRY.cache_info()
    b = REGISTRY.select("vtanh", x, policy="pallas", target="rvv-128")
    info2 = REGISTRY.cache_info()
    assert a is b
    assert info2["hits"] == info1["hits"] + 1
    assert info2["misses"] == info1["misses"]
    # different target / policy / shape => distinct cache entries
    REGISTRY.select("vtanh", x, policy="pallas", target="rvv-256")
    REGISTRY.select("vtanh", x, policy="vector", target="rvv-128")
    REGISTRY.select("vtanh", jnp.zeros((65, 64)), policy="pallas",
                    target="rvv-128")
    assert REGISTRY.cache_info()["misses"] == info2["misses"] + 3


def test_selection_cache_accounting_invariant():
    """Regression: the stat books must balance.  Shrinking the cache via
    set_cache_capacity counts its evictions, and lookups whose key is
    poisoned by an unhashable argument land in 'uncacheable' — never
    silently in neither bucket — so hits + misses + uncacheable ==
    lookups always holds."""
    x = jnp.zeros((32, 32), jnp.float32)
    REGISTRY.cache_clear()
    old_cap = REGISTRY.cache_info()["capacity"]
    try:
        # five distinct entries, then shrink to 2: three shrink-evictions
        for i in range(5):
            REGISTRY.select("vadd", jnp.zeros(16 + i), jnp.zeros(16 + i),
                            policy="pallas", target="rvv-128")
        assert REGISTRY.cache_info()["size"] == 5
        REGISTRY.set_cache_capacity(2)
        info = REGISTRY.cache_info()
        assert info["size"] == 2
        assert info["evictions"] == 3, \
            "shrink-evictions must be counted like insert-evictions"
        # an unhashable kwarg poisons the key: selection still answers,
        # the lookup books as uncacheable (not a miss, never a hit)
        before = REGISTRY.cache_info()
        a = REGISTRY.select("vadd", x, x, policy="pallas",
                            target="rvv-128", meta={"un": "hashable"})
        b = REGISTRY.select("vadd", x, x, policy="pallas",
                            target="rvv-128", meta={"un": "hashable"})
        assert a.tier == b.tier == "vector"
        info = REGISTRY.cache_info()
        assert info["uncacheable"] == before["uncacheable"] + 2
        assert info["hits"] == before["hits"]
        assert info["misses"] == before["misses"]
        # the invariant the autotune layer keys off
        assert info["lookups"] == \
            info["hits"] + info["misses"] + info["uncacheable"]
        # cache_clear resets every counter, including the new bucket
        REGISTRY.cache_clear()
        info = REGISTRY.cache_info()
        assert (info["hits"], info["misses"], info["evictions"],
                info["uncacheable"], info["lookups"]) == (0, 0, 0, 0, 0)
    finally:
        REGISTRY.set_cache_capacity(old_cap)


def test_explain_report_shape():
    x = jnp.zeros((128, 128), jnp.float32)
    rep = explain("vsigmoid", x, policy="pallas", target="rvv-128")
    assert rep["op"] == "vsigmoid" and rep["target"] == "rvv-128"
    assert rep["chosen"] == "pallas" and rep["chosen_cost"] > 0
    tiers = [c["tier"] for c in rep["candidates"]]
    assert tiers == sorted(tiers, key=["generic", "vector", "pallas"].index)
    chosen = [c for c in rep["candidates"] if c["chosen"]]
    assert len(chosen) == 1 and chosen[0]["tier"] == "pallas"


def test_listing8_costlier_customized_rejected():
    """The real Listing-8 property: given an *actual* customized
    candidate that models worse than one vector op, the selector keeps
    the vector tier (vadd alone can't show this — it registers no
    customized tier at all)."""
    from repro.core.registry import register

    @register("__l8_add", "vector", cost=trace.vector_cost(1))
    def _v(a, b):
        return a + b

    @register("__l8_add", "pallas", cost=trace.vector_cost(3),
              doc="pointlessly customized: 3 ops where 1 suffices")
    def _p(a, b):
        return a + b

    x = jnp.zeros(1024, jnp.float32)
    for name in targets.RVV_FAMILY + ("tpu-v5e",):
        assert REGISTRY.select("__l8_add", x, x, policy="pallas",
                               target=name).tier == "vector", name


def test_dispatch_accepts_target_kwarg():
    """dispatch(target=...) must steer selection without leaking the
    kwarg into the lowering function."""
    from repro.core.registry import dispatch
    x = jnp.asarray([1.0, 2.0])
    out = dispatch("vadd", x, x, target="rvv-128")
    np.testing.assert_array_equal(np.asarray(out), [2.0, 4.0])


def test_cache_keys_on_target_value_not_name():
    """An ad-hoc Target sharing a registered name must not hit the
    other machine's cache entry."""
    q = jnp.zeros(16, jnp.uint8)
    REGISTRY.cache_clear()
    assert REGISTRY.select("vrbit", q, policy="pallas",
                           target="rvv-64").tier == "generic"
    import dataclasses
    wide = dataclasses.replace(targets.get_target("rvv-64"), vlen=1024)
    assert REGISTRY.select("vrbit", q, policy="pallas",
                           target=wide).tier == "pallas"


def test_counting_uses_selection_cost(caplog):
    """dispatch under trace.count() reuses the memoized selection-time
    cost — and the counted value matches the declared model."""
    x = jnp.zeros(4096, jnp.uint8)
    with use_target("rvv-128"):
        with trace.count() as c:
            with use_policy("pallas"):
                isa.vrbit(x)
        low = REGISTRY.select("vrbit", x, policy="pallas")
        assert c["total"] == int(low.cost(x))


def test_validity_evaluated_under_requested_target():
    """supports predicates (e.g. VMEM budgets) must see the requested
    target, not the ambient one — and the cache must not memoize a
    selection made against the wrong machine."""
    x = jnp.zeros((1, 200, 200, 64), jnp.float32)   # ~10 MiB fp32 slab
    w = jnp.zeros((3, 3, 64, 64), jnp.float32)
    REGISTRY.cache_clear()

    def pallas_valid(rep):
        return next(c["valid"] for c in rep["candidates"]
                    if c["tier"] == "pallas")

    # ambient tpu-v5e (16 MiB VMEM): slab+acc exceed the scratch budget
    assert not pallas_valid(explain("conv_hwc", x, w, policy="pallas"))
    # explicit tpu-v6 (32 MiB): fits — even though ambient is still v5e
    assert pallas_valid(explain("conv_hwc", x, w, policy="pallas",
                                target="tpu-v6"))
    # select with target= agrees with select inside use_target (the
    # cache must never memoize an ambient-target decision under the
    # requested target's key)
    a = REGISTRY.select("conv_hwc", x, w, policy="pallas", target="tpu-v6")
    with use_target("tpu-v6"):
        b = REGISTRY.select("conv_hwc", x, w, policy="pallas")
    assert a is b


def test_widening_ops_declare_output_width():
    """vcombine/vzip produce a register wider than their operands; the
    Table-2 rule must fail them on a target that can hold the inputs
    but not the result (D+D -> Q needs vlen >= 128)."""
    d = jnp.zeros(2, jnp.int32)                     # int32x2_t: 64-bit D
    assert REGISTRY.select("vcombine", d, d, policy="pallas",
                           target="rvv-64").tier == "generic"
    assert REGISTRY.select("vcombine", d, d, policy="pallas",
                           target="rvv-128").tier == "vector"
    assert REGISTRY.select("vzip", d, d, policy="pallas",
                           target="rvv-64").tier == "generic"
    assert REGISTRY.select("vzip", d, d, policy="pallas",
                           target="rvv-128").tier == "pallas"


def test_tpu_baseline_column_has_no_union_overhead():
    """The beyond-paper TPU baseline is the plain XLA jaxpr count — no
    SIMDe union round-trip (XLA fuses it away), no scalarized libm."""
    vrelu = _figure2_column("tpu-v5e")["vrelu"]
    # jnp.clip on (1024,1024) fp32: 2 eqns x 1024 vregs, 1x (no union)
    assert vrelu["baseline_instrs"] == 2048


def test_figure2_ops_choose_customized_on_rvv128():
    """Acceptance: on rvv-128 the selector chooses the customized
    lowering for the ten XNNPACK functions with baseline/customized > 1,
    vtanh/vsigmoid the largest (paper Figure-2 ordering); simple
    arithmetic keeps the vector tier."""
    _assert_figure2_selection("rvv-128")
    assert len(_figure2_column("rvv-128")) == len(figure2.FIGURE2_OPS)


# ---------------------------------------------------------------------------
# Figure 2 pinned: every column's counts and tiers, exactly
# ---------------------------------------------------------------------------

FIGURE2_COLUMNS = targets.RVV_FAMILY + ("tpu-v5e",)


@functools.lru_cache(maxsize=None)
def _figure2_column(target):
    return {r["name"]: r for r in figure2.column(target)}


def _assert_figure2_selection(target):
    """The paper's Figure-2 selection properties on an RVV target."""
    with use_target(target):
        # Listing 8: the selector must KEEP the vector tier for simple
        # arithmetic — a customized kernel cannot beat one vector op.
        probe = jnp.zeros((1024,), jnp.float32)
        arith = REGISTRY.explain("vadd", probe, probe, policy="pallas")
    assert arith["chosen"] == "vector", arith
    rows = _figure2_column(target)
    ratio = {name: r["baseline_instrs"] / max(1, r["customized_instrs"])
             for name, r in rows.items()}
    for name in figure2.FIGURE2_OPS:
        assert rows[name]["customized_tier"] == "pallas", \
            f"{name}: selector kept {rows[name]['customized_tier']}, " \
            f"not customized"
        assert ratio[name] > 1.0, \
            f"{name}: customized not cheaper ({ratio[name]:.2f}x)"
    top2 = sorted(ratio, key=lambda n: -ratio[n])[:2]
    assert set(top2) == {"vtanh", "vsigmoid"}, \
        f"largest wins should be vtanh/vsigmoid, got {top2}"


@pytest.mark.parametrize("target", targets.RVV_FAMILY)
def test_figure2_selection_across_rvv_family(target):
    _assert_figure2_selection(target)


@pytest.mark.parametrize("op", figure2.FIGURE2_OPS)
@pytest.mark.parametrize("target", FIGURE2_COLUMNS)
def test_figure2_counts_match_golden(target, op):
    """The counts are a deterministic cost-model evaluation, not a
    measurement: any drift in a tier or an instruction count is a
    reviewed change of ``tests/golden/figure2_counts.json``."""
    row = _figure2_column(target)[op]
    fresh = {k: row[k] for k in ("baseline_instrs", "customized_instrs",
                                 "baseline_tier", "customized_tier")}
    assert fresh == FIGURE2_GOLDEN[target][op], \
        f"{op}@{target} drifted from the golden; fresh row: {fresh}"


# ---------------------------------------------------------------------------
# Hardened cost models (scalar operands) + vget_high parity
# ---------------------------------------------------------------------------

def test_cost_models_accept_scalar_operands():
    assert trace.scalar_cost(3)(2.5) == 3
    assert trace.vector_cost(2)(0.5, (8,)) == 2
    with trace.count() as c:
        isa.vdup(0.5, (8,))
    assert c["total"] >= 1          # previously swallowed as 0


def test_broken_cost_model_logs_once(caplog):
    bad = Lowering(op="__bad", tier="vector", fn=lambda x: x,
                   cost=lambda *a, **k: 1 / 0)
    trace._cost_warned.discard(("__bad", "vector", "cost model"))
    with caplog.at_level(logging.WARNING, logger="repro.core.trace"):
        with trace.count() as c:
            trace.record(bad, jnp.zeros(4))
            trace.record(bad, jnp.zeros(4))
    warnings = [r for r in caplog.records if "__bad" in r.getMessage()]
    assert len(warnings) == 1       # logged once, not swallowed
    assert c["total"] == 0


def test_broken_supports_predicate_logs_once(caplog):
    """A raising ``supports`` predicate still marks the lowering invalid,
    but says so once instead of dropping the tier without a word."""
    bad = Lowering(op="__badpred", tier="pallas", fn=lambda x: x,
                   supports=lambda x, extra: True)   # wrong arity
    trace._cost_warned.discard(("__badpred", "pallas", "supports predicate"))
    with caplog.at_level(logging.WARNING, logger="repro.core.trace"):
        assert not bad.ok(jnp.zeros(4))
        assert not bad.ok(jnp.zeros(4))
    warnings = [r for r in caplog.records if "__badpred" in r.getMessage()]
    assert len(warnings) == 1
    assert "supports predicate" in warnings[0].getMessage()


# ---------------------------------------------------------------------------
# LMUL>1 register grouping (rvv-*-m2/m4/m8)
# ---------------------------------------------------------------------------

def test_lmul_variants_registered():
    for bits in (64, 128, 256, 512, 1024):
        for m in (2, 4, 8):
            t = targets.get_target(f"rvv-{bits}-m{m}")
            assert t.lmul == m and t.vlen == bits
    assert targets.get_target("rvv-128").lmul == 1


def test_lmul_grows_register_group():
    m1 = targets.get_target("rvv-128")
    m4 = targets.get_target("rvv-128-m4")
    assert m4.vreg_elems(jnp.float32) == 4 * m1.vreg_elems(jnp.float32)


def test_lmul_widens_mappable_registers():
    """Grouping relaxes the Table-2 rule: lmul * vlen >= width."""
    assert not targets.get_target("rvv-64").supports_width(128)
    assert targets.get_target("rvv-64-m2").supports_width(128)
    assert targets.get_target("rvv-64-m2").supports_width(256) is False
    assert targets.get_target("rvv-64-m8").supports_width(512)


def test_lmul_does_not_understate_wide_op_cost():
    """A grouped instruction retires lmul register micro-ops: grouping
    must not let the cost model claim an lmul-x dynamic speedup, and a
    part-filled group costs *more* than ungrouped issue."""
    m1 = targets.get_target("rvv-128")
    m4 = targets.get_target("rvv-128-m4")
    # full groups: same total micro-ops either way
    assert m4.vinstrs(64, jnp.float32) == m1.vinstrs(64, jnp.float32)
    # one Q register on an LMUL=4 config wastes 3 register passes
    assert m4.vinstrs(4, jnp.float32) == 4
    assert m1.vinstrs(4, jnp.float32) == 1


def test_lmul_threads_through_traced_cost():
    x = jnp.zeros((16,), jnp.float32)      # one vreg at m4, 4 at m1
    f = lambda a: a + a
    with use_target("rvv-128"):
        m1_count = trace.jaxpr_vector_instrs(f, x)
    with use_target("rvv-128-m4"):
        m4_count = trace.jaxpr_vector_instrs(f, x)
    assert m1_count == 4 and m4_count == 4   # 1 grouped instr x lmul


def test_with_lmul_helper():
    t = targets.with_lmul("rvv-256", 4)
    assert t.name == "rvv-256-m4" and t.lmul == 4
    assert targets.with_lmul(t, 1).name == "rvv-256"
    with pytest.raises(ValueError):
        targets.with_lmul("rvv-128", 3)
    with pytest.raises(ValueError):
        targets.with_lmul("tpu-v5e", 2)


# ---------------------------------------------------------------------------
# Bounded (LRU) selection cache
# ---------------------------------------------------------------------------

def test_selection_cache_is_bounded():
    info = REGISTRY.cache_info()
    assert info["capacity"] >= 1 and "evictions" in info
    old_cap = info["capacity"]
    REGISTRY.cache_clear()
    try:
        REGISTRY.set_cache_capacity(3)
        for i in range(8):
            REGISTRY.select("vadd", jnp.zeros(4 + i), jnp.zeros(4 + i),
                            policy="pallas", target="rvv-128")
        info = REGISTRY.cache_info()
        assert info["size"] <= 3
        assert info["evictions"] == 8 - 3
    finally:
        REGISTRY.set_cache_capacity(old_cap)
        REGISTRY.cache_clear()


def test_selection_cache_lru_keeps_hot_entries():
    old_cap = REGISTRY.cache_info()["capacity"]
    REGISTRY.cache_clear()
    try:
        REGISTRY.set_cache_capacity(2)
        hot = jnp.zeros(100)
        REGISTRY.select("vadd", hot, hot, policy="pallas",
                        target="rvv-128")
        for i in range(4):
            # touch the hot entry between one-shot fillers: it must
            # survive every eviction round
            REGISTRY.select("vadd", jnp.zeros(4 + i), jnp.zeros(4 + i),
                            policy="pallas", target="rvv-128")
            before = REGISTRY.cache_info()["hits"]
            REGISTRY.select("vadd", hot, hot, policy="pallas",
                            target="rvv-128")
            assert REGISTRY.cache_info()["hits"] == before + 1
    finally:
        REGISTRY.set_cache_capacity(old_cap)
        REGISTRY.cache_clear()


def test_set_cache_capacity_validates():
    with pytest.raises(ValueError):
        REGISTRY.set_cache_capacity(0)


@pytest.mark.parametrize("shape", [(8,), (3, 8), (2, 3, 8), (2, 2, 3, 8)])
def test_vget_high_generic_pallas_parity(shape):
    """Generic and customized (slidedown) lowerings agree for any rank —
    the old vmap(...).T generic path corrupted ndim > 2 layouts."""
    rng = np.random.default_rng(int(np.prod(shape)))
    x = jnp.asarray(rng.integers(-100, 100, shape).astype(np.int32))
    with use_policy("generic"):
        g = isa.vget_high(x)
    with use_policy("pallas"):
        c = isa.vget_high(x)
    n = shape[-1]
    np.testing.assert_array_equal(np.asarray(g), np.asarray(x[..., n // 2:]))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(c))


# ---------------------------------------------------------------------------
# explicit target= through the model-level ops (multi-backend serving)
# ---------------------------------------------------------------------------

def test_ops_accept_explicit_target():
    """attention/ssd/gemm take target= and the selection is made against
    that machine — not the ambient thread-scoped target."""
    import jax
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 8, 2, 16)).astype(np.float32))
    base = np.asarray(ops.attention(q, k, v, causal=True))
    for tgt in ("rvv-128", "tpu-v5e"):
        out = np.asarray(ops.attention(q, k, v, causal=True, target=tgt))
        np.testing.assert_allclose(out, base, rtol=2e-5, atol=1e-5)
    a = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(ops.gemm(a, b, target="rvv-256")),
        np.asarray(a @ b), rtol=1e-5, atol=1e-5)


def test_forward_threads_target_per_request():
    """model.forward(target=...) pins every attention/ssd selection for
    that request; selections against the explicit target actually land
    in the cache keyed on it."""
    import jax
    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config("gemma3-1b").reduced()
    key = jax.random.PRNGKey(0)
    params = M.init(cfg, key)
    tokens = jax.random.randint(key, (1, 8), 2, cfg.vocab_size)
    amb, _, _ = M.forward(params, cfg, {"tokens": tokens}, mode="train")
    for tgt in ("rvv-1024", "tpu-v5e"):
        out, _, _ = M.forward(params, cfg, {"tokens": tokens},
                              mode="train", target=tgt)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32)),
            np.asarray(amb.astype(jnp.float32)), rtol=5e-2, atol=5e-2)
