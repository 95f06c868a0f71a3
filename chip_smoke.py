#!/usr/bin/env python3
"""Drive the system's three main paths once on a TPU chip.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded train step, four chips

On one chip the phases run in this order, each printing one line:

  device   the first device must be a TPU whose kind maps to a Target;
  port     the 24-kernel NEON corpus served through ``PortEngine`` on
           rvv-128 and rvv-1024, checked against the harness references,
           with every degradation counter at 0;
  pallas   the ten XNNPACK conversions through their registered Pallas
           lowerings, each executable holding a ``tpu_custom_call``, checked
           against ``kernels/ref.py``;
  model    gemma2-2b at published widths (random weights from ``--seed``)
           served through ``serve.Engine``: prefill 4 x 512, decode 16;
           flash attention must run as a kernel, and the Pallas-tier
           logits must agree with the vector tier's; the decode
           attention kernel runs at the decode shapes against its
           vector tier.

``--chips 4`` runs only one gemma2-2b train step on a (2, 2) data x model
mesh and the same step on one device of the host, and compares the loss.

The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed.  Off a TPU the script exits
non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(REPO, "src"), REPO,
                os.path.join(REPO, "examples", "neon_corpus")]

B, PROMPT, GEN = 4, 512, 16          # model phase: batch, prompt, decode
# bf16 model through 26 layers: the two tiers' last-position logits may
# differ by a few bf16 roundings per layer; relative L2 bound
LOGITS_REL_L2 = 3e-2
# one decode-attention call, bf16 output of an f32 softmax: relative L2
ATTN_REL_L2 = 1e-2
# the train-step loss of the sharded and single-device runs (bf16 psums
# on the mesh vs local sums): relative bound
LOSS_RTOL = 1e-2
TRAIN_LAYERS = 2                     # one local + one global layer
TRAIN_BATCH, TRAIN_SEQ = 8, 128


def log(phase: str, msg: str) -> None:
    print(f"{phase}: {msg}", flush=True)


def pallas_kernels(hlo_text: str) -> set:
    """Names of the Pallas kernels a compiled TPU executable calls."""
    names = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names.update(re.findall(r"jit\((\w+)\)/pallas_call", line))
    return names


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(want_count: int):
    import jax
    from repro.core import targets
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"device: found platform {d.platform!r} "
                         f"({d.device_kind}); this script needs a TPU")
    if len(devs) < want_count:
        raise SystemExit(f"device: {len(devs)} devices, need {want_count}")
    tgt = targets.device_target(d.device_kind)
    targets.set_default_target(tgt)
    log("device", f"platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)} target={tgt.name}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_port(seed: int) -> None:
    import numpy as np
    import harness
    from repro import port
    from repro.serve import PortEngine, Request

    corpus = port.load_corpus(harness.CORPUS_DIR)
    cases = harness.cases(n=4096, tail_n=4093, seed=seed)
    reqs, wants = [], []
    for i, case in enumerate(cases):
        args = case.make_args(np.random.default_rng(seed + i))
        want = case.reference(*args)
        for tgt in ("rvv-128", "rvv-1024"):
            reqs.append(Request(corpus[case.kernel], args, target=tgt))
            wants.append((case, want, tgt))
    eng = PortEngine(policy="pallas", revec=True)
    times = []
    for _ in range(2):          # cold (compiles), then warm
        t0 = time.perf_counter()
        outs = eng.submit(reqs)
        times.append(time.perf_counter() - t0)
        for (case, want, tgt), got in zip(wants, outs):
            if isinstance(got, Exception):
                raise RuntimeError(f"{case.kernel}/{tgt}: {got!r}")
            harness.assert_conforms(got, want, case, f"{case.kernel}/{tgt}")
    s = eng.stats()
    counters = {k: s[k] for k in ("batch_faults", "row_fallbacks",
                                  "program_fallbacks", "errors_returned")}
    counters["fallback_rungs"] = sum(
        s["resilience"]["ladder"]["fallback_rungs"].values())
    if any(counters.values()):
        raise RuntimeError(f"degraded serving: {counters}")
    log("port", f"{len(cases)} kernels x rvv-128,rvv-1024 through PortEngine"
        f"(policy=pallas, revec) == harness references; degradation "
        f"counters {counters}; batch_programs={s['batch_programs']}; "
        f"chip_width_programs={s['chip_width_programs']}; "
        f"slate cold {times[0]:.1f}s (compiles) warm {times[1]:.3f}s")


def phase_pallas() -> None:
    import jax
    import numpy as np
    from benchmarks import xnnpack_suite
    from repro.core.registry import REGISTRY
    from repro.kernels import ref

    errs, bad = {}, []
    t0 = time.perf_counter()
    for name, op, args, kw in xnnpack_suite.workloads():
        f, arrs = xnnpack_suite.array_fn(REGISTRY.lowering(op, "pallas").fn,
                                         args, kw)
        compiled = jax.jit(f).lower(*arrs).compile()
        if "tpu_custom_call" not in compiled.as_text():
            bad.append(f"{name}: no tpu_custom_call")
        got = jax.tree.leaves(compiled(*arrs))
        r, _ = xnnpack_suite.array_fn(getattr(ref, op), args, kw)
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(jax.jit(r)(*arrs))
        worst = 0.0
        for g, w in zip(got, want):
            g = np.asarray(g, np.float64)
            w = np.asarray(w, np.float64)
            # error relative to the output's scale (f32 kernels; the
            # MXU ones accumulate in f32)
            worst = max(worst, float(np.max(np.abs(g - w)) /
                                     max(1.0, float(np.max(np.abs(w))))))
        errs[name] = worst
        if not worst <= 2e-4:
            bad.append(f"{name}: scaled error {worst:.3g} > 2e-4")
    dt = time.perf_counter() - t0
    if bad:
        raise RuntimeError("; ".join(bad) + f" (errors {errs})")
    log("pallas", f"{len(errs)} XNNPACK conversions, Pallas lowering vs "
        f"kernels/ref.py, all with tpu_custom_call; max scaled error "
        f"{max(errs.values()):.3g} ({min(errs, key=errs.get)}.."
        f"{max(errs, key=errs.get)}); compile+run {dt:.1f}s")


def phase_model(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core.registry import REGISTRY, use_policy
    from repro.models import model as M
    from repro.serve.engine import Engine, make_prefill_step, make_serve_step

    cfg = get_config("gemma2-2b")
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    params = jax.jit(M.init, static_argnums=0)(cfg, key)
    jax.block_until_ready(params)
    t_init = time.perf_counter() - t0
    prompts = jax.random.randint(jax.random.fold_in(key, 1), (B, PROMPT),
                                 2, cfg.vocab_size)

    # served through the Engine, as launch/serve.py does
    eng = Engine(cfg, params, max_batch=B, max_seq=PROMPT + GEN)
    t0 = time.perf_counter()
    first = jax.block_until_ready(eng.prefill(prompts))
    t_pre_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    first = jax.block_until_ready(eng.prefill(prompts))
    t_pre = time.perf_counter() - t0
    t0 = time.perf_counter()
    toks = [eng.decode(first, 1)]
    t_dec_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    toks.append(eng.decode(jnp.asarray(toks[0][:, -1]), GEN - 1))
    t_dec = (time.perf_counter() - t0) / (GEN - 1)
    toks = np.concatenate(toks, axis=1)
    if toks.shape != (B, GEN) or toks.min() < 0 or \
            toks.max() >= cfg.vocab_size:
        raise RuntimeError(f"decoded tokens out of range: {toks.shape}, "
                           f"[{toks.min()}, {toks.max()}]")
    log("model", f"{cfg.name} ({cfg.n_layers}L d{cfg.d_model} vocab "
        f"{cfg.vocab_size} {cfg.dtype}) Engine: prefill "
        f"{B}x{PROMPT} cold {t_pre_cold:.1f}s warm {t_pre * 1e3:.1f} ms; "
        f"decode {GEN} tokens, first step {t_dec_cold:.1f}s then "
        f"{t_dec * 1e3:.2f} ms/token; init {t_init:.1f}s (information only)")

    # the same two step functions the Engine jits: which kernels they call
    cache = M.init_cache(cfg, B, PROMPT + GEN)
    batch = {"tokens": prompts}
    pre = jax.jit(make_prefill_step(cfg)).lower(params, cache, batch).compile()
    dec = jax.jit(make_serve_step(cfg)).lower(
        params, cache, prompts[:, :1], jnp.full((B,), PROMPT, jnp.int32)
    ).compile()
    k_pre = pallas_kernels(pre.as_text())
    k_dec = pallas_kernels(dec.as_text())
    if "flash_attention" not in k_pre or not k_dec:
        raise RuntimeError(f"Pallas kernels missing: prefill {sorted(k_pre)}"
                           f", decode {sorted(k_dec)}")
    logits_p = np.asarray(pre(params, cache, batch)[0], np.float32)
    with use_policy("vector"):
        vec = jax.jit(make_prefill_step(cfg)).lower(params, cache,
                                                    batch).compile()
    if pallas_kernels(vec.as_text()):
        raise RuntimeError("vector-tier prefill still calls Pallas kernels")
    logits_v = np.asarray(vec(params, cache, batch)[0], np.float32)
    if not (np.all(np.isfinite(logits_p)) and np.all(np.isfinite(logits_v))):
        raise RuntimeError("non-finite logits")
    rel = float(np.linalg.norm(logits_p - logits_v) /
                np.linalg.norm(logits_v))
    agree = float(np.mean(logits_p.argmax(-1) == logits_v.argmax(-1)))
    if not rel <= LOGITS_REL_L2:
        raise RuntimeError(f"Pallas vs vector logits: relative L2 {rel:.3g}"
                           f" > {LOGITS_REL_L2}")
    log("model", f"prefill kernels {sorted(k_pre)}, decode kernels "
        f"{sorted(k_dec)}; last-position logits Pallas vs vector tier: "
        f"relative L2 {rel:.3g} <= {LOGITS_REL_L2}, max |diff| "
        f"{float(np.max(np.abs(logits_p - logits_v))):.3g}, argmax "
        f"agreement {agree:.2f}")

    # the selector may keep the vector tier for decode attention at these
    # shapes; run its Pallas kernel on the same operands all the same
    kq, kk, kv = jax.random.split(jax.random.fold_in(key, 2), 3)
    q = jax.random.normal(kq, (B, 1, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    kvs = (B, PROMPT + GEN, cfg.n_kv_heads, cfg.head_dim)
    k = jax.random.normal(kk, kvs, jnp.bfloat16)
    v = jax.random.normal(kv, kvs, jnp.bfloat16)
    lens = jnp.full((B,), PROMPT, jnp.int32)
    outs = {}
    for tier in ("pallas", "vector"):
        fn = REGISTRY.lowering("decode_attention", tier).fn
        c = jax.jit(lambda q, k, v, n, fn=fn: fn(
            q, k, v, n, cfg.window, cfg.softcap)).lower(
            q, k, v, lens).compile()
        if (tier == "pallas") != bool(pallas_kernels(c.as_text())):
            raise RuntimeError(f"decode attention {tier} tier: kernels "
                               f"{sorted(pallas_kernels(c.as_text()))}")
        outs[tier] = np.asarray(c(q, k, v, lens), np.float32)
    rel_d = float(np.linalg.norm(outs["pallas"] - outs["vector"]) /
                  np.linalg.norm(outs["vector"]))
    if not rel_d <= ATTN_REL_L2:
        raise RuntimeError(f"decode attention Pallas vs vector: relative L2 "
                           f"{rel_d:.3g} > {ATTN_REL_L2}")
    log("model", f"decode attention kernel at decode shapes q {q.shape} "
        f"kv {kvs}: Pallas vs vector tier relative L2 {rel_d:.3g} <= "
        f"{ATTN_REL_L2}")


def phase_train_mesh(seed: int) -> None:
    import jax
    from repro.configs import get_config
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.models import sharding as Sh
    from repro.optim import adamw
    from repro.train.loop import (TrainConfig, make_sharded_train_step,
                                  make_train_step, opt_state_pspecs)

    # published widths; depth cut so the single-device run fits one chip
    cfg = get_config("gemma2-2b").replace(n_layers=TRAIN_LAYERS)
    tcfg = TrainConfig()
    batch = SyntheticLM(cfg.vocab_size, TRAIN_SEQ, TRAIN_BATCH,
                        seed=seed).batch(0)
    init = jax.jit(M.init, static_argnums=0)
    key = jax.random.PRNGKey(seed)

    mesh = make_mesh((2, 2), ("data", "model"))
    params = init(cfg, key)
    step = make_sharded_train_step(cfg, tcfg, mesh, params, batch)
    params = Sh.shard_params(params, mesh, cfg)
    opt = jax.jit(adamw.init, out_shardings=Sh.ns(
        mesh, opt_state_pspecs(params, cfg, mesh)))(params)
    t0 = time.perf_counter()
    _, _, _, m = step(params, opt, None, batch)
    loss_mesh = float(m["loss"])
    t_mesh = time.perf_counter() - t0
    del params, opt

    single = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    params = init(cfg, key)
    opt = jax.jit(adamw.init)(params)
    t0 = time.perf_counter()
    _, _, _, m = single(params, opt, None, batch)
    loss_one = float(m["loss"])
    t_one = time.perf_counter() - t0
    if not abs(loss_mesh - loss_one) <= LOSS_RTOL * abs(loss_one):
        raise RuntimeError(f"train loss: mesh {loss_mesh} vs one device "
                           f"{loss_one}")
    log("train", f"gemma2-2b widths, {TRAIN_LAYERS} of 26 layers, batch "
        f"{TRAIN_BATCH}x{TRAIN_SEQ}: loss on (2,2) data x model mesh "
        f"{loss_mesh:.6f} vs one device {loss_one:.6f} (rtol {LOSS_RTOL}); "
        f"step with compile: mesh {t_mesh:.1f}s, one device {t_one:.1f}s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch import enable_compile_cache
    cache_dir = enable_compile_cache()
    device = phase_device(args.chips)
    log("device", f"compile cache {cache_dir}")
    phases = ([("train", lambda: phase_train_mesh(args.seed))]
              if args.chips == 4 else
              [("port", lambda: phase_port(args.seed)),
               ("pallas", phase_pallas),
               ("model", lambda: phase_model(args.seed))])
    failed = []
    t_all = time.perf_counter()
    for name, run in phases:
        try:
            run()
        except Exception as e:  # noqa: BLE001 — report every phase
            failed.append(name)
            log(name, f"FAILED {type(e).__name__}: {e}")
    log("done", f"{len(phases) - len(failed)}/{len(phases)} phases passed "
        f"in {time.perf_counter() - t_all:.1f}s")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
