"""Paper Figure-2 reproduction: the ten XNNPACK functions, customized
lowering vs original-SIMDe baseline, swept across the RVV width family.

Metric = dynamic vector-instruction count (the paper's Spike methodology;
see core/trace.py).  Both columns now come straight from the cost-driven
selector (core/registry.py):

  baseline   — the ladder choice under the ``use_policy('vector')`` cap
               (original SIMDe: customized conversions excluded, highest
               valid tier wins); the vector tier's cost model analyzes
               its own jaxpr with the generic-union 2x memory round-trip
               and, on targets without a vector libm, scalarized
               transcendentals (paper §3.2/§4.2),
  customized — unconstrained selection; on the RVV family the selector
               picks the customized (pallas-tier) lowering for all ten
               functions by evaluated cost, while *keeping the vector
               tier for simple arithmetic* (paper Listing 8) — asserted
               below via a vadd probe.

``explain()`` exposes the per-candidate analysis table behind each row.
``main()`` sweeps rvv-128/256/512/1024 (+ the beyond-paper TPU column)
and writes BENCH_xnnpack.json so the perf trajectory is machine-readable.

Workload sizes follow XNNPACK microkernel benchmark conventions
(MobileNet-ish layer shapes).
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp

from repro.core import targets, trace, use_target
from repro.core.registry import REGISTRY, TIERS
from repro.kernels import ops  # noqa: F401  (registers kernel lowerings)

KEY = jax.random.PRNGKey(0)

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


def run_target(target, check=False):
    """One Figure-2 column: per-op baseline vs selector-chosen lowering
    under ``target``, straight from the selection engine's cost models.

    ``check``: assert the paper's selection properties (only meaningful
    on the RVV family, where the baseline toolchain model applies).
    """
    target = targets.get_target(target)
    rows = []
    with use_target(target):
        # Listing 8: the selector must KEEP the vector tier for simple
        # arithmetic — a customized kernel cannot beat one vector op.
        probe = jnp.zeros((1024,), jnp.float32)
        arith = REGISTRY.explain("vadd", probe, probe, policy="pallas")
        if check:
            assert arith["chosen"] == "vector", arith
        for name, opname, args, kw in workloads():
            base = REGISTRY.explain(opname, *args, policy="vector", **kw)
            cust = REGISTRY.explain(opname, *args, policy="pallas", **kw)
            # Original SIMDe is a preprocessor *ladder*, not a cost
            # search: its baseline is the highest valid tier under the
            # cap (the vector port), even where the scalar loop would
            # model cheaper.
            ladder = max((c for c in base["candidates"]
                          if c["valid"] and c["cost"] is not None),
                         key=lambda c: TIERS.index(c["tier"]))
            ratio = ladder["cost"] / max(1, cust["chosen_cost"])
            rows.append({
                "name": name, "target": target.name,
                "baseline_tier": ladder["tier"],
                "customized_tier": cust["chosen"],
                "baseline_instrs": int(ladder["cost"]),
                "customized_instrs": int(cust["chosen_cost"]),
                "speedup": round(ratio, 2),
                "candidates": cust["candidates"],
            })
        if check:
            _check_figure2(rows)
    return rows


def _check_figure2(rows):
    """The paper's Figure-2 selection properties on an RVV target."""
    by_name = {r["name"]: r for r in rows}
    for name in FIGURE2_OPS:
        r = by_name[name]
        assert r["customized_tier"] == "pallas", \
            f"{name}: selector kept {r['customized_tier']}, not customized"
        assert r["speedup"] > 1.0, \
            f"{name}: customized not cheaper ({r['speedup']}x)"
    top2 = sorted(rows, key=lambda r: -r["speedup"])[:2]
    assert {t["name"] for t in top2} == {"vtanh", "vsigmoid"}, \
        f"largest wins should be vtanh/vsigmoid, got {[t['name'] for t in top2]}"


def run_rvv_sweep(check=True):
    """Sweep the paper's VLA width family — Figure 2 at every vlen."""
    return {w: run_target(w, check=check) for w in targets.RVV_FAMILY}


# ---------------------------------------------------------------------------
# Beyond-paper TPU column: instruction selection (MXU) + fusion (HBM)
# ---------------------------------------------------------------------------

def _kernel_io_bytes(opname, args, kw, out):
    arrays = [a for a in args if hasattr(a, "shape")]
    outs = jax.tree.leaves(out)
    return trace.io_bytes(*arrays, *outs)


def run_tpu(target="tpu-v5e"):
    """The adapted target: the baseline has a vector libm and XLA fuses
    away the SIMDe union round-trip, so the baseline column is the
    *un-overheaded* jaxpr count of the vector tier and the win is
    instruction selection (MXU macro-ops) + fusion (HBM traffic)."""
    rows = []
    with use_target(target):
        for name, opname, args, kw in workloads():
            cust = REGISTRY.explain(opname, *args, policy="pallas", **kw)
            low_v = REGISTRY.select(opname, *args, policy="vector", **kw)
            base_instrs = trace.jaxpr_vector_instrs(
                low_v.fn, *args, scalarize=False, union_overhead=False, **kw)
            f, arrs = array_fn(low_v.fn, args, kw)
            out = jax.eval_shape(f, *arrs)
            base_bytes = trace.jaxpr_hbm_bytes(low_v.fn, *args, **kw)
            cust_bytes = _kernel_io_bytes(opname, args, kw, out)
            rows.append({
                "name": name, "target": targets.get_target(target).name,
                "baseline_tier": low_v.tier,
                "customized_tier": cust["chosen"],
                "baseline_instrs": int(base_instrs),
                "customized_instrs": int(cust["chosen_cost"]),
                "speedup": round(base_instrs
                                 / max(1, cust["chosen_cost"]), 2),
                "baseline_bytes": int(base_bytes),
                "customized_bytes": int(cust_bytes),
                "traffic_ratio": round(base_bytes / max(1, cust_bytes), 2),
            })
    return rows


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

def emit_json(sweep, tpu_rows, path="BENCH_xnnpack.json"):
    """Machine-readable perf trajectory: per-op baseline/customized
    dynamic instruction counts + ratio, per target width."""
    data = {"suite": "xnnpack_figure2",
            "metric": "dynamic_vector_instructions",
            "targets": {}}
    tpu_name = tpu_rows[0]["target"] if tpu_rows else "tpu"
    for tname, rows in list(sweep.items()) + [(tpu_name, tpu_rows)]:
        data["targets"][tname] = {
            r["name"]: {k: r[k] for k in
                        ("baseline_tier", "customized_tier",
                         "baseline_instrs", "customized_instrs", "speedup")
                        } | ({"traffic_ratio": r["traffic_ratio"]}
                             if "traffic_ratio" in r else {})
            for r in rows}
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
    return path


def check_regression(data, baseline_path="BENCH_xnnpack.json"):
    """Exact-count gate vs the committed baseline.

    The metric is a deterministic cost-model evaluation, not a
    measurement — so the tolerance is ZERO: tiers must match and
    instruction counts must be *identical*.  (The ibilinear baseline
    drifted from PR 2's committed counts without tripping anything
    because this gate didn't exist; any intentional cost-model change
    now shows up as a reviewed baseline diff, never a silent shift.)
    """
    if not os.path.exists(baseline_path):
        print(f"# no committed {baseline_path}; skipping regression gate")
        return
    with open(baseline_path) as f:
        base = json.load(f)
    problems = []
    for tname, ops in base.get("targets", {}).items():
        fresh_ops = data["targets"].get(tname)
        if fresh_ops is None:
            problems.append(f"{tname}: target column disappeared")
            continue
        for name, row in ops.items():
            fr = fresh_ops.get(name)
            if fr is None:
                problems.append(f"{name}@{tname}: op disappeared")
                continue
            for key in ("baseline_instrs", "customized_instrs",
                        "baseline_tier", "customized_tier"):
                if fr[key] != row[key]:
                    problems.append(
                        f"{name}@{tname}: {key} {row[key]!r} -> "
                        f"{fr[key]!r}")
    if problems:
        raise AssertionError(
            "BENCH_xnnpack drift vs committed baseline (cost models are "
            "deterministic — every diff is a reviewed change):\n  "
            + "\n  ".join(problems))
    print(f"# regression gate vs {baseline_path}: exact match OK")


def main(json_path="BENCH_xnnpack.json", regression=False):
    sweep = run_rvv_sweep(check=True)
    print("# RVV cost model sweep (paper Figure 2 reproduction)")
    print(f"{'function':12s}", *(f"{w:>10s}" for w in targets.RVV_FAMILY))
    for i, name in enumerate(FIGURE2_OPS):
        cells = [f"{sweep[w][i]['speedup']:>9.2f}x" for w in targets.RVV_FAMILY]
        print(f"{name:12s}", *cells)
    sp = [r["speedup"] for r in sweep["rvv-128"]]
    print(f"# rvv-128 range: {min(sp):.2f}x .. {max(sp):.2f}x "
          f"(paper: 1.51x .. 5.13x)\n")

    tpu_rows = run_tpu()
    print("# TPU v5e cost model (beyond-paper adaptation)")
    print(f"{'function':12s} {'chosen':>8s} {'instr-speedup':>14s} "
          f"{'HBM-traffic-x':>14s}")
    for r in tpu_rows:
        print(f"{r['name']:12s} {r['customized_tier']:>8s} "
              f"{r['speedup']:>13.2f}x {r['traffic_ratio']:>13.2f}x")

    if regression:
        # gate BEFORE overwriting the committed baseline
        tpu_name = tpu_rows[0]["target"] if tpu_rows else "tpu"
        fresh = {"targets": {
            tname: {r["name"]: r for r in rows}
            for tname, rows in list(sweep.items()) + [(tpu_name,
                                                       tpu_rows)]}}
        check_regression(fresh, baseline_path=json_path)
    path = emit_json(sweep, tpu_rows, json_path)
    print(f"\n# wrote {path}")
    # legacy contract for benchmarks/run.py: 'rvv128' mirrors rvv-128
    out = {w: sweep[w] for w in sweep}
    out["rvv128"] = sweep["rvv-128"]
    out["tpu"] = tpu_rows
    return out


if __name__ == "__main__":
    main(regression="--check" in sys.argv[1:])
