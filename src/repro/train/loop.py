"""Training: step builder (grad-accum scan, sharded) + supervised loop.

``make_train_step`` builds the jit-able pure function; it is what the
multi-pod dry-run lowers.  ``train`` wires data, checkpointing, watchdog
and restart supervision around it (the deployable driver).
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpointer as ckpt
from repro.core.registry import use_policy
from repro.data.pipeline import SyntheticLM, extra_inputs
from repro.kernels import ref
from repro.models import model as M
from repro.models import sharding as Sh
from repro.optim import adamw, compression
from repro.runtime.fault_tolerance import FailureInjector, Supervisor, Watchdog

log = logging.getLogger("repro.train")


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    accum: int = 1                    # gradient-accumulation microbatches
    aux_coef: float = 0.01            # MoE load-balance coefficient
    compress_grads: bool = False      # int8 error-feedback compression
    optim: adamw.AdamWConfig = dataclasses.field(
        default_factory=adamw.AdamWConfig)


def loss_fn(params, cfg, batch, sp_spec=None):
    logits, _, aux = M.forward(params, cfg, batch, mode="train",
                               sp_spec=sp_spec)
    xent = ref.softmax_xent(logits, batch["targets"])
    return jnp.mean(xent) + 0.01 * aux, (jnp.mean(xent), aux)


def make_train_step(cfg, tcfg: TrainConfig, mesh=None):
    """(params, opt_state, err_state, batch) -> (params, opt, err, metrics).

    The batch leading dim is split into ``tcfg.accum`` microbatches and
    scanned (grad accumulation): peak activation memory is one
    microbatch's, which is the knob that fits the 123B arch.
    """
    sp_spec = None
    if mesh is not None and cfg.use_sp:
        from jax.sharding import NamedSharding
        sp_spec = NamedSharding(mesh, Sh.activation_spec(mesh, cfg))

    def step(params, opt_state, err_state, batch):
        accum = tcfg.accum

        def micro(i):
            return jax.tree.map(
                lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:])[i],
                batch)

        def accum_body(carry, i):
            gsum, lsum, asum = carry
            (l, (xent, aux)), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, cfg, micro(i), sp_spec)
            gsum = jax.tree.map(jnp.add, gsum, g)
            return (gsum, lsum + xent, asum + aux), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        # the Pallas lowerings define no backward pass (pallas_call has
        # no transpose rule), so the differentiated forward is traced
        # under the vector tier on every backend
        with Sh.active_mesh(mesh), use_policy("vector"):
            (gsum, lsum, asum), _ = jax.lax.scan(
                accum_body, (zeros, jnp.zeros(()), jnp.zeros(())),
                jnp.arange(accum))
        grads = jax.tree.map(lambda g: g / accum, gsum)

        if tcfg.compress_grads:
            packed, err_state = compression.compress(grads, err_state)
            grads = compression.decompress(packed)

        params, opt_state, om = adamw.update(grads, opt_state, params,
                                             tcfg.optim)
        metrics = {"loss": lsum / accum, "aux": asum / accum, **om}
        return params, opt_state, err_state, metrics

    return step


def opt_state_pspecs(params_sds, cfg, mesh):
    """PartitionSpecs of the AdamW state (``adamw.init``) on ``mesh``."""
    spec = Sh.opt_pspecs(params_sds, cfg, mesh)
    return {"m": spec, "v": spec, "master": spec, "step": P()}


def make_sharded_train_step(cfg, tcfg: TrainConfig, mesh, params_sds,
                            batch_sds):
    """jit the step with explicit in/out shardings for the mesh."""
    pspecs = Sh.param_pspecs(params_sds, cfg, mesh)
    ospecs = opt_state_pspecs(params_sds, cfg, mesh)
    espec = Sh.opt_pspecs(params_sds, cfg, mesh) if tcfg.compress_grads \
        else None
    bspec = jax.tree.map(lambda _: Sh.token_spec(mesh), batch_sds)
    step = make_train_step(cfg, tcfg, mesh)
    return jax.jit(
        step,
        in_shardings=(Sh.ns(mesh, pspecs), Sh.ns(mesh, ospecs),
                      None if espec is None else Sh.ns(mesh, espec),
                      Sh.ns(mesh, bspec)),
        out_shardings=(Sh.ns(mesh, pspecs), Sh.ns(mesh, ospecs),
                       None if espec is None else Sh.ns(mesh, espec), None),
        donate_argnums=(0, 1) if espec is None else (0, 1, 2),
    )


def train(cfg, *, steps: int, batch_size: int = 8, seq_len: int = 128,
          tcfg: Optional[TrainConfig] = None, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, seed: int = 0,
          injector: Optional[FailureInjector] = None,
          log_every: int = 10) -> Dict[str, Any]:
    """Single-host training driver with checkpoint/restart + watchdog."""
    tcfg = tcfg or TrainConfig()
    data = SyntheticLM(cfg.vocab_size, seq_len, batch_size, seed=seed)
    extra = extra_inputs(cfg, batch_size, seed)
    key = jax.random.PRNGKey(seed)
    params0 = M.init(cfg, key)
    opt0 = adamw.init(params0)
    err0 = compression.err_init(params0) if tcfg.compress_grads else None
    step_fn = jax.jit(make_train_step(cfg, tcfg))

    saver = ckpt.AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
    watchdog = Watchdog()
    history = []

    def resume_step() -> int:
        if ckpt_dir:
            s = ckpt.latest_step(ckpt_dir)
            return 0 if s is None else s + 1
        return 0

    state = {"params": params0, "opt": opt0, "err": err0}

    def body(start: int) -> int:
        nonlocal state
        if start > 0:
            tpl = {"params": params0, "opt": opt0}
            loaded = ckpt.restore(ckpt_dir, start - 1, tpl)
            state["params"], state["opt"] = loaded["params"], loaded["opt"]
            log.info("resumed from step %d", start - 1)
        for s in range(start, steps):
            if injector is not None:
                injector.maybe_fail(s)
            batch = {**data.batch(s), **extra}
            watchdog.start()
            state["params"], state["opt"], state["err"], m = step_fn(
                state["params"], state["opt"], state["err"], batch)
            m = jax.device_get(m)
            watchdog.stop(s)
            history.append({"step": s, **{k: float(v) for k, v in m.items()}})
            if s % log_every == 0:
                log.info("step %d loss %.4f", s, float(m["loss"]))
            if saver and (s % ckpt_every == 0 or s == steps - 1):
                saver.save(s, {"params": state["params"], "opt": state["opt"]})
        if saver:
            saver.wait()
        return steps - 1

    sup = Supervisor()
    sup.run(body, resume_step)
    return {"history": history, "watchdog": watchdog.incidents,
            "restarts": sup.restarts, "params": state["params"]}
