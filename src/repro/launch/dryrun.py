"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell the appropriate step function (train_step for train shapes,
prefill/serve_step for inference shapes) is jit'd with the production
shardings and lowered against ShapeDtypeStruct stand-ins — no allocation.
``compiled.memory_analysis()`` proves the per-device footprint fits,
``cost_analysis()`` + HLO collective parsing feed §Roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
      --shape train_4k --mesh single          # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun.json               # the full matrix
"""
import argparse
import json
import os
import re
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, SHAPES, get_config
from repro.data.pipeline import extra_inputs
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models import sharding as Sh
from repro.optim import adamw
from repro.serve.engine import make_prefill_step, make_serve_step
from repro.train.loop import (TrainConfig, loss_fn, make_train_step,
                              opt_state_pspecs)

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def tree_sds(tree):
    return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)


# ---------------------------------------------------------------------------
# accumulation / batch policy per cell (the memory-fit knob)
# ---------------------------------------------------------------------------

def accum_for(cfg, shape) -> int:
    if shape.kind != "train":
        return 1
    if cfg.d_model >= 12_000:
        # §Perf iteration 5: FSDP param-gather traffic scales with accum
        # (2 gathers x params x accum); SP shards the saved per-layer
        # boundary activations 16-way, so accum=4 fits the 16 GB budget
        a = 4 if cfg.use_sp else 16
    elif cfg.d_model >= 5_000:
        a = 8
    elif cfg.d_model >= 2_000:
        a = 4
    else:
        a = 2
    if cfg.vocab_size >= 100_000:
        a = max(a, 8)   # big-vocab logits dominate activation memory
    return a


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    b, s = shape.global_batch, shape.seq_len
    specs = {"tokens": sds((b, s if shape.kind != "decode" else 1),
                           jnp.int32)}
    if shape.kind == "train":
        specs["targets"] = sds((b, s), jnp.int32)
    if cfg.family == "encdec" and shape.kind != "decode":
        specs["frames"] = sds((b, cfg.n_frames, cfg.d_model), jnp.float32)
    if cfg.family == "vlm" and shape.kind != "decode":
        specs["patches"] = sds((b, cfg.n_patches, cfg.d_model), jnp.float32)
    return cfg, shape, specs


# ---------------------------------------------------------------------------
# lowering per cell kind
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, mesh):
    cfg, shape, batch_specs = input_specs(arch, shape_name)
    b, s = shape.global_batch, shape.seq_len
    params_sds = jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0)))
    pspecs = Sh.param_pspecs(params_sds, cfg, mesh)
    bspec = {k: Sh.fit_spec(
        P(Sh.batch_axes(mesh), *([None] * (len(v.shape) - 1))),
        v.shape, mesh) for k, v in batch_specs.items()}

    if shape.kind == "train":
        tcfg = TrainConfig(accum=accum_for(cfg, shape))
        opt_sds = jax.eval_shape(adamw.init, params_sds)
        ospecs = opt_state_pspecs(params_sds, cfg, mesh)
        step = make_train_step(cfg, tcfg, mesh)
        fn = lambda p, o, batch: step(p, o, None, batch)[:2]
        jfn = jax.jit(fn,
                      in_shardings=(Sh.ns(mesh, pspecs), Sh.ns(mesh, ospecs),
                                    Sh.ns(mesh, bspec)),
                      out_shardings=(Sh.ns(mesh, pspecs), Sh.ns(mesh, ospecs)))
        with mesh:
            lowered = jfn.lower(params_sds, opt_sds, batch_specs)
        return lowered, {"accum": tcfg.accum}

    # serving cells
    p_off = cfg.n_patches if cfg.family == "vlm" else 0
    cache_sds = jax.eval_shape(
        lambda: M.init_cache(cfg, b, s + p_off))
    cspecs = Sh.cache_pspecs(cache_sds, mesh)

    if shape.kind == "prefill":
        step = make_prefill_step(cfg)

        def fn(p, c, batch):
            with Sh.active_mesh(mesh):
                return step(p, c, batch)

        jfn = jax.jit(fn,
                      in_shardings=(Sh.ns(mesh, pspecs), Sh.ns(mesh, cspecs),
                                    Sh.ns(mesh, bspec)),
                      out_shardings=(None, Sh.ns(mesh, cspecs)))
        with mesh:
            lowered = jfn.lower(params_sds, cache_sds, batch_specs)
        return lowered, {}

    # decode: one new token against a seq_len cache
    step = make_serve_step(cfg)
    lspec = Sh.fit_spec(P(Sh.batch_axes(mesh)), (b,), mesh)

    def fn(p, c, tokens, lengths):
        with Sh.active_mesh(mesh):
            return step(p, c, tokens, lengths)

    jfn = jax.jit(fn,
                  in_shardings=(Sh.ns(mesh, pspecs), Sh.ns(mesh, cspecs),
                                Sh.ns(mesh, bspec["tokens"]),
                                Sh.ns(mesh, lspec)),
                  out_shardings=(None, Sh.ns(mesh, cspecs)))
    with mesh:
        lowered = jfn.lower(params_sds, cache_sds, batch_specs["tokens"],
                            sds((b,), jnp.int32))
    return lowered, {}


# ---------------------------------------------------------------------------
# analysis: trip-count-aware HLO accounting + XLA memory/cost analysis
# ---------------------------------------------------------------------------

def analyze(lowered, compiled):
    from repro.launch import hlo_analysis
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    txt = compiled.as_text()
    hlo = hlo_analysis.analyze(txt)
    return {
        # per-device, trip-count corrected (see hlo_analysis.py)
        "flops": float(hlo["flops"]),
        "bytes_accessed": float(hlo["bytes"]),
        "collective_bytes": hlo["collectives"],
        "collective_total": float(hlo["collective_total"]),
        "scan_trips": hlo["whiles"],
        # raw XLA numbers (loop bodies counted once) for cross-checking
        "xla_flops_static": float(cost.get("flops", 0.0)),
        "xla_bytes_static": float(cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)),
        },
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             mesh_shape=None):
    """mesh_shape: optional (data, model) remap of the same 256 chips —
    used by §Perf iterations; the production contract stays (16, 16)."""
    cfg = get_config(arch)
    mesh_name = f"pod{mesh_shape[0]}x{mesh_shape[1]}" if mesh_shape else \
        ("pod2x16x16" if multi_pod else "pod16x16")
    if shape_name in cfg.skip_shapes:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped",
                "reason": "full-attention arch at 500k cache (DESIGN.md)"}
    t0 = time.time()
    try:
        if mesh_shape is not None:
            from repro.launch.mesh import make_mesh
            mesh = make_mesh(tuple(mesh_shape), ("data", "model"))
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
        lowered, meta = lower_cell(arch, shape_name, mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        rec = analyze(lowered, compiled)
        rec.update({"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "status": "ok", "lower_s": round(t_lower, 1),
                    "compile_s": round(t_compile, 1),
                    "n_devices": mesh.devices.size, **meta})
        total, active = cfg.param_counts()
        rec["params_total"] = total
        rec["params_active"] = active
        return rec
    except Exception as e:  # noqa: BLE001
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}


def main():
    # the production meshes need 512 host devices; set before the
    # backend starts (nothing imported above touches it)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--mesh-shape", default=None,
                    help="perf-iteration remap, e.g. '64,4'")
    args = ap.parse_args()
    mesh_shape = tuple(int(x) for x in args.mesh_shape.split(",")) \
        if args.mesh_shape else None

    archs = ARCH_NAMES if args.all or not args.arch else (args.arch,)
    shapes = tuple(SHAPES) if args.all or not args.shape else (args.shape,)
    meshes = {"single": (False,), "multi": (True,),
              "both": (False, True)}[args.mesh]

    results = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r["status"] in ("ok", "skipped")}

    for multi in meshes:
        mesh_name = "pod2x16x16" if multi else "pod16x16"
        for arch in archs:
            for shape in shapes:
                if (arch, shape, mesh_name) in done:
                    continue
                rec = run_cell(arch, shape, multi_pod=multi,
                               mesh_shape=mesh_shape)
                results = [r for r in results if
                           (r["arch"], r["shape"], r["mesh"]) !=
                           (arch, shape, mesh_name)] + [rec]
                line = {k: v for k, v in rec.items() if k != "trace"}
                print(json.dumps(line), flush=True)
                if args.out:
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1)
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    err = sum(r["status"] == "error" for r in results)
    print(f"# dry-run: {ok} ok, {sk} skipped, {err} errors")
    return 0 if err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
