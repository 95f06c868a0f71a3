"""Conversion-ladder dispatch (paper §3.1/3.3) + instruction counting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core import isa, registry, trace, use_policy
from repro.core.registry import REGISTRY


def test_ladder_order():
    low = REGISTRY.select("vrbit", jnp.zeros(8, jnp.uint8), policy="pallas")
    assert low.tier == "pallas"
    low = REGISTRY.select("vrbit", jnp.zeros(8, jnp.uint8), policy="vector")
    assert low.tier == "generic"  # no vector tier for vrbit -> falls through
    low = REGISTRY.select("vadd", jnp.zeros(8), jnp.zeros(8), policy="pallas")
    assert low.tier == "vector"   # simple arithmetic keeps vector (Listing 8)


def test_policy_scoping():
    assert REGISTRY.policy in registry.TIERS
    with use_policy("generic"):
        assert REGISTRY.policy == "generic"
        with use_policy("pallas"):
            assert REGISTRY.policy == "pallas"
        assert REGISTRY.policy == "generic"


def test_unknown_op():
    with pytest.raises(KeyError):
        REGISTRY.select("no_such_op", policy="vector")


@given(st.lists(st.integers(0, 255), min_size=1, max_size=64))
@settings(max_examples=40, deadline=None)
def test_vrbit_tiers_agree(vals):
    """Customized binary-magic lowering == scalar oracle (Listing 7)."""
    x = jnp.asarray(vals, jnp.uint8)
    with use_policy("generic"):
        g = isa.vrbit(x)
    with use_policy("pallas"):
        c = isa.vrbit(x)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(c))


@given(st.lists(st.integers(-1000, 1000), min_size=2, max_size=32).filter(
    lambda v: len(v) % 2 == 0))
@settings(max_examples=30, deadline=None)
def test_vget_high_tiers_agree(vals):
    x = jnp.asarray(vals, jnp.int32)
    with use_policy("generic"):
        g = isa.vget_high(x)
    with use_policy("pallas"):
        c = isa.vget_high(x)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(c))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(x[len(vals)//2:]))


def test_vceq_matches_neon_semantics():
    a = jnp.asarray([1, 2, 3, 4], jnp.int32)
    b = jnp.asarray([1, 0, 3, 0], jnp.int32)
    with use_policy("pallas"):
        r = isa.vceq(a, b)
    np.testing.assert_array_equal(
        np.asarray(r), np.asarray([0xFFFFFFFF, 0, 0xFFFFFFFF, 0], np.uint32))


def test_instruction_counting_ratio():
    """Customized vrbit beats the scalarized baseline in dynamic instrs —
    the paper's Figure-2 methodology at op granularity."""
    x = jnp.zeros(4096, jnp.uint8)
    with trace.count() as base:
        with use_policy("generic"):
            isa.vrbit(x)
    with trace.count() as cust:
        with use_policy("pallas"):
            isa.vrbit(x)
    assert base["total"] > cust["total"] > 0
    assert base["total"] / cust["total"] > 10


def test_jaxpr_instr_estimator():
    n = 4096
    f = lambda x: jnp.tanh(x)
    x = jnp.zeros(n, jnp.float32)
    vec = trace.jaxpr_vector_instrs(f, x, scalarize=False)
    sca = trace.jaxpr_vector_instrs(f, x, scalarize=True)
    assert sca == trace.PRIM_SCALAR_COST["tanh"] * n  # scalar libm calls
    assert vec == trace.VEC_EXPANSION["tanh"] * (n // 1024)  # vector poly
    # dot: 256x512 @ 512x256 => ceil-based MXU macro ops
    g = lambda a, b: a @ b
    a = jnp.zeros((256, 512), jnp.float32)
    b = jnp.zeros((512, 256), jnp.float32)
    assert trace.jaxpr_vector_instrs(g, a, b) == (256 // 128) ** 2 * (512 // 128)
    # RVV-width model: fma ladder instead of MXU macro-ops
    with trace.cost_target("rvv-128"):
        assert trace.jaxpr_vector_instrs(g, a, b) == 256 * 512 * 256 // 4


def test_isa_semantics_against_numpy():
    rng = np.random.default_rng(0)
    a = rng.integers(-100, 100, 16).astype(np.int32)
    b = rng.integers(-100, 100, 16).astype(np.int32)
    ja, jb = jnp.asarray(a), jnp.asarray(b)
    np.testing.assert_array_equal(np.asarray(isa.vadd(ja, jb)), a + b)
    np.testing.assert_array_equal(np.asarray(isa.vmax(ja, jb)),
                                  np.maximum(a, b))
    np.testing.assert_array_equal(np.asarray(isa.vpadd(ja, jb)),
                                  np.concatenate([a, b]).reshape(-1, 2).sum(1))
    np.testing.assert_array_equal(np.asarray(isa.vaddv(ja)), a.sum())
    np.testing.assert_array_equal(np.asarray(isa.vzip(ja, jb)),
                                  np.stack([a, b], -1).reshape(-1))
    np.testing.assert_array_equal(np.asarray(isa.vext(ja, jb, 3)),
                                  np.concatenate([a[3:], b[:3]]))
    rev = np.asarray(isa.vrev64(jnp.asarray(a)))
    np.testing.assert_array_equal(rev, a.reshape(-1, 2)[:, ::-1].reshape(-1))


def test_import_leaves_backend_alone():
    """Importing the model stack must not initialise a JAX backend (on a
    TPU host that takes the chip); the default policy is resolved on the
    first dispatch instead.  Run in a fresh CPU-only interpreter."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    prog = (
        "from jax._src import xla_bridge as xb\n"
        "import repro.models.model, repro.kernels.ops, repro.serve\n"
        "assert not xb._backends, sorted(xb._backends)\n"
        "from repro.core.registry import REGISTRY\n"
        "print(REGISTRY.policy)\n")
    r = subprocess.run([sys.executable, "-c", prog],
                       env=dict(os.environ, PYTHONPATH=src,
                                JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["vector"]


@pytest.mark.parametrize("policy", ["generic", "vector", "pallas"])
@pytest.mark.parametrize("op,dst,lo,hi", [("vqmovn", jnp.int8, -128, 127),
                                          ("vqmovun", jnp.uint8, 0, 255)])
def test_saturating_narrow_clamps_in_32_bits(policy, op, dst, lo, hi):
    """XLA:TPU clamps the negative lanes of an int16 arithmetic right
    shift to the upper bound (``clip(x >> 5, -128, 127)`` on int16);
    every tier of the saturating narrows therefore clamps in int32."""
    x = jnp.arange(-600, 600, 7, dtype=jnp.int16)
    fn = getattr(isa, op)
    with use_policy(policy):
        text = str(jax.make_jaxpr(lambda a: fn(a, dst))(x))
        got = np.asarray(fn(x, dst))
    clamps = [ln for ln in text.splitlines()
              if " = max " in ln or " = min " in ln]
    assert clamps and all(":i32[" in ln for ln in clamps), clamps
    np.testing.assert_array_equal(got, np.clip(np.asarray(x), lo, hi)
                                  .astype(dst))
