"""GPipe-style pipeline parallelism over a 'pipe' mesh axis (optional).

The 40-cell production matrix uses DP x TP (x SP/EP/FSDP), which is the
right fit for <=123B params; this module provides the PP building block
for deeper-than-memory models: stages own contiguous layer groups,
microbatches stream through a ``shard_map`` loop whose inter-stage hop
is a single ``ppermute`` (the collective the TPU ICI torus does best),
giving the classic (M + S - 1)-tick schedule with bubble fraction
(S-1)/(M+S-1).

``pipeline(stage_fn, stage_params, x, mesh)`` is schedule-only: it makes
no assumption about what a stage computes.  Validated by
tests/test_pipeline.py (equivalence vs sequential stage application on a
4-stage host mesh).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline(stage_fn, stage_params, x_mb, mesh, *, axis: str = "pipe"):
    """Run microbatches through pipeline stages.

    stage_fn: (params_one_stage, x_mb) -> y_mb (same shape family)
    stage_params: pytree stacked on a leading (S,) stage axis
    x_mb: (M, mb, ...) microbatches
    mesh: mesh containing ``axis`` with S ranks

    Returns (M, mb, ...) outputs (stage S-1's results, replicated).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    s = sizes[axis]
    m = x_mb.shape[0]
    ticks = m + s - 1
    perm = [(i, i + 1) for i in range(s - 1)]

    def ranked(params_l, xs):
        idx = jax.lax.axis_index(axis)
        params_one = jax.tree.map(lambda a: a[0], params_l)
        carry = jnp.zeros_like(xs[0])        # inter-stage register
        outs = jnp.zeros((ticks,) + xs.shape[1:], xs.dtype)

        def tick(t, state):
            carry, outs = state
            feed = jnp.where(t < m, t, m - 1)
            inp = jnp.where(idx == 0, xs[feed], carry)
            out = stage_fn(params_one, inp)
            outs = outs.at[t].set(jnp.where(idx == s - 1, out, 0))
            carry = jax.lax.ppermute(out, axis, perm)
            return carry, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (carry, outs))
        # only the last stage produced real outputs; broadcast them
        outs = jax.lax.psum(outs, axis)      # all-zero elsewhere
        return outs

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params,
                             is_leaf=lambda a: hasattr(a, "ndim")), P())
    out = jax.shard_map(ranked, mesh=mesh, in_specs=in_specs, out_specs=P(),
                        check_vma=False)(stage_params, x_mb)
    # outputs for microbatch j emerge at tick j + s - 1
    return out[s - 1:]


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
