"""Plain float32 Mamba2 language model (arXiv:2405.21060), for checking a
served model's tokens.

Each layer, as the published Mamba2 block computes it:

    h            = rmsnorm(x) * ln
    z, xBC, dt   = split(h @ W_in)
    xBC          = silu(causal_conv1d(xBC, conv_w) + conv_b)
    x_, B, C     = split(xBC)
    dt           = softplus(dt + dt_bias);   A = -exp(A_log)
    S_t          = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t       (sequential)
    y_t          = C_t . S_t + D x_t
    y            = rmsnorm(y * silu(z)) * gn                     (gated norm)
    x            = x + y @ W_out

then ``logits = rmsnorm(x) * norm_f @ emb^T`` (tied embedding).  Every
matrix product runs at ``highest`` precision, the recurrence is a plain
scan over time, and there is no kernel, cache or batching trick.  The
weights are arrays handed in by the caller; nothing here imports the
program.  ``gate="sigmoid"`` computes ``y * sigmoid(z)`` in the gated norm
in place of the published ``y * silu(z)``; it exists only so that a test
can show that this is the one place where a program that gates so
departs from this reference.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

LAYER_KEYS = ("ln", "w_in", "conv_w", "conv_b", "A_log", "D", "dt_bias",
              "gn", "w_out")


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


@functools.partial(jax.jit, static_argnames=("dims", "gate", "eps"))
def layer(x, w: Dict, *, dims, gate="silu", eps=1e-5):
    """One Mamba2 block on x:(R, T, d) float32."""
    di, g, n, h, p, k = dims
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        r, t, _ = x.shape
        u = _rmsnorm(x, f32(w["ln"]), eps)
        zxbcdt = u @ f32(w["w_in"])
        z = zxbcdt[..., :di]
        xbc = zxbcdt[..., di:2 * di + 2 * g * n]
        dt = zxbcdt[..., 2 * di + 2 * g * n:]
        cw = f32(w["conv_w"])                              # (k, conv_dim)
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(padded[:, i:i + t] * cw[i] for i in range(k))
        xbc = jax.nn.silu(conv + f32(w["conv_b"]))
        xs = xbc[..., :di].reshape(r, t, h, p)
        bm = jnp.repeat(xbc[..., di:di + g * n].reshape(r, t, g, n),
                        h // g, axis=2)
        cm = jnp.repeat(xbc[..., di + g * n:].reshape(r, t, g, n),
                        h // g, axis=2)
        dt = jax.nn.softplus(dt + f32(w["dt_bias"]))         # (R, T, h)
        a = -jnp.exp(f32(w["A_log"]))

        def step(state, inp):
            x_t, b_t, c_t, dt_t = inp
            state = (state * jnp.exp(dt_t * a)[..., None, None] +
                     (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
            return state, jnp.einsum("rhpn,rhn->rhp", state, c_t)

        s0 = jnp.zeros((r, h, p, n), jnp.float32)
        seq = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(bm, 1, 0),
               jnp.moveaxis(cm, 1, 0), jnp.moveaxis(dt, 1, 0))
        _, y = jax.lax.scan(step, s0, seq)
        y = jnp.moveaxis(y, 0, 1) + f32(w["D"])[:, None] * xs
        y = y.reshape(r, t, di)
        gz = jax.nn.silu(z) if gate == "silu" else jax.nn.sigmoid(z)
        y = _rmsnorm(y * gz, f32(w["gn"]), eps)
        return x + y @ f32(w["w_out"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_f, emb, *, eps=1e-5):
    with jax.default_matmul_precision("highest"):
        u = _rmsnorm(x, jnp.asarray(norm_f, jnp.float32), eps)
        return u @ jnp.asarray(emb, jnp.float32).T


def dims_of(model: Dict):
    d = model["d_model"]
    di = model["expand"] * d
    return (di, model["ngroups"], model["d_state"], di // model["headdim"],
            model["headdim"], model["d_conv"])


def hidden(weights: Dict, tokens: np.ndarray, model: Dict, *,
           gate="silu", eps=1e-5) -> jax.Array:
    """Final hidden states (R, T, d) float32 for token rows (R, T).
    ``weights``: ``emb`` (V, d), ``norm_f`` (d,), and ``layers``, a dict
    of ``LAYER_KEYS`` whose arrays stack the layers on their first axis."""
    emb = weights["emb"]
    x = jnp.asarray(emb, jnp.float32)[jnp.asarray(tokens)]
    dims = dims_of(model)
    stack = weights["layers"]
    for i in range(model["n_layer"]):
        w = {k: stack[k][i] for k in LAYER_KEYS}
        x = layer(x, w, dims=dims, gate=gate, eps=eps)
    return x


def logits_at(weights: Dict, x: jax.Array, positions: np.ndarray,
              vocab: int, *, eps=1e-5) -> np.ndarray:
    """Logits over the first ``vocab`` rows of the embedding at
    ``positions`` (R, P) of hidden states x:(R, T, d); float32 (R, P, V)."""
    rows = jnp.arange(x.shape[0])[:, None]
    sel = x[rows, jnp.asarray(positions)]
    return np.asarray(_head(sel, weights["norm_f"], weights["emb"][:vocab],
                            eps=eps))
