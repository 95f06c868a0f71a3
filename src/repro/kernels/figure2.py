"""The paper's Figure 2: the ten XNNPACK functions, customized lowering
vs original-SIMDe baseline, as dynamic vector-instruction counts.

The metric is the paper's Spike methodology (see core/trace.py).  Both
columns come straight from the cost-driven selector (core/registry.py):

  baseline   — on the RVV family, the ladder choice under the
               ``use_policy('vector')`` cap (original SIMDe: customized
               conversions excluded, highest valid tier wins); the
               vector tier's cost model analyzes its own jaxpr with the
               generic-union 2x memory round-trip and, on targets
               without a vector libm, scalarized transcendentals (paper
               §3.2/§4.2).  On a TPU (beyond the paper) the baseline has
               a vector libm and XLA fuses the union round-trip away, so
               it is the plain jaxpr count of the vector tier, and the
               win is instruction selection (MXU macro-ops);
  customized — unconstrained selection.

Workload sizes follow XNNPACK microkernel benchmark conventions
(MobileNet-ish layer shapes).  ``tests/golden/figure2_counts.json``
pins every column's counts and tiers exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import targets, trace, use_target
from repro.core.registry import REGISTRY, TIERS
from repro.kernels import ops  # noqa: F401  (registers kernel lowerings)

# The ten ops of the paper's Figure 2, in its plot order.
FIGURE2_OPS = ("gemm", "convhwc", "dwconv", "maxpool", "argmaxpool",
               "vrelu", "vsqrt", "vtanh", "vsigmoid", "ibilinear")


def _r(shape, seed=0, scale=1.0, dtype=jnp.float32):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape) * scale
            ).astype(dtype)


def workloads():
    """(name, op, args, kwargs) — one per paper benchmark function."""
    img = _r((56, 56, 64), 1)
    p = 56 * 56
    iy = jax.random.randint(jax.random.PRNGKey(2), (p,), 0, 54)
    ix = jax.random.randint(jax.random.PRNGKey(3), (p,), 0, 54)
    wy = jax.random.uniform(jax.random.PRNGKey(4), (p,))
    wx = jax.random.uniform(jax.random.PRNGKey(5), (p,))
    big = _r((1, 56, 56, 256), 6)
    return [
        ("gemm", "gemm", (_r((256, 512), 7), _r((512, 256), 8),
                          _r((256,), 9), -1.0, 1.0), {}),
        ("convhwc", "conv_hwc", (_r((1, 28, 28, 128), 10),
                                 _r((3, 3, 128, 128), 11, 0.1),
                                 _r((128,), 12)), {}),
        ("dwconv", "dwconv", (_r((1, 56, 56, 128), 13),
                              _r((3, 3, 128), 14, 0.3),
                              _r((128,), 15)), {}),
        ("maxpool", "maxpool", (big, (2, 2)), {}),
        ("argmaxpool", "argmaxpool", (big, (2, 2)), {}),
        ("vrelu", "vrelu", (_r((1024, 1024), 16), 0.0, 6.0), {}),
        ("vsqrt", "vsqrt", (jnp.abs(_r((1024, 1024), 17)) + 0.01,), {}),
        ("vtanh", "vtanh", (_r((1024, 1024), 18, 2.0),), {}),
        ("vsigmoid", "vsigmoid", (_r((1024, 1024), 19, 2.0),), {}),
        ("ibilinear", "ibilinear", (img, iy, ix, wy, wx), {}),
    ]


def array_fn(fn, args, kw):
    """``fn`` as a function of the array arguments among ``args`` (the
    rest are closed over), and those arrays — what ``jax.jit`` and
    ``jax.eval_shape`` take."""
    is_arr = [hasattr(a, "shape") for a in args]

    def f(*arrs):
        it = iter(arrs)
        return fn(*[next(it) if ok else a for a, ok in zip(args, is_arr)],
                  **kw)

    return f, [a for a, ok in zip(args, is_arr) if ok]


def column(target):
    """One Figure-2 column: per op, the baseline and the selector-chosen
    customized lowering under ``target``, from the selection engine's
    cost models."""
    target = targets.get_target(target)
    rows = []
    with use_target(target):
        for name, opname, args, kw in workloads():
            cust = REGISTRY.explain(opname, *args, policy="pallas", **kw)
            row = {"name": name, "target": target.name,
                   "customized_tier": cust["chosen"],
                   "customized_instrs": int(cust["chosen_cost"])}
            if target.vla:
                # Original SIMDe is a preprocessor *ladder*, not a cost
                # search: its baseline is the highest valid tier under
                # the cap (the vector port), even where the scalar loop
                # would model cheaper.
                base = REGISTRY.explain(opname, *args, policy="vector",
                                        **kw)
                ladder = max((c for c in base["candidates"]
                              if c["valid"] and c["cost"] is not None),
                             key=lambda c: TIERS.index(c["tier"]))
                row.update(baseline_tier=ladder["tier"],
                           baseline_instrs=int(ladder["cost"]))
            else:
                low_v = REGISTRY.select(opname, *args, policy="vector",
                                        **kw)
                row.update(
                    baseline_tier=low_v.tier,
                    baseline_instrs=int(trace.jaxpr_vector_instrs(
                        low_v.fn, *args, scalarize=False,
                        union_overhead=False, **kw)))
            rows.append(row)
    return rows
