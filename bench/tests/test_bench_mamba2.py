"""The plain Mamba2 reference at a tiny size on the CPU: against itself,
against the published Mamba2 of ``transformers``, and against the
program, whose gated norm departs from the published block (the reason
the benchmark has no Mamba2 cell yet)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.refs import mamba2 as ref

TINY = {"d_model": 64, "n_layer": 2, "vocab_size": 100, "d_state": 16,
        "d_conv": 4, "expand": 2, "headdim": 16, "ngroups": 1,
        "chunk_size": 8, "tie_embeddings": True}


def _program_config():
    """The program's mamba2-1.3b configuration at the tiny sizes."""
    from repro.configs import get_config
    m = TINY
    return get_config("mamba2-1.3b").replace(
        n_layers=m["n_layer"], d_model=m["d_model"],
        vocab_size=m["vocab_size"], ssm_state=m["d_state"],
        ssm_headdim=m["headdim"], ssm_groups=m["ngroups"],
        ssm_conv=m["d_conv"], ssm_chunk=m["chunk_size"],
        ssm_expand=m["expand"], dtype="float32", tie_embeddings=True)


def _leaf(key, name, shape):
    """Mamba2's initial distributions (A in U[1, 16], dt log-uniform in
    [1e-3, 1e-1]), fan-in scaled projections, and norm and skip weights
    in U[0.5, 1.5] so that no multiply by them is a no-op."""
    if name == "emb":
        return jax.random.normal(key, shape) * 0.02
    if name in ("w_in", "w_out"):
        return jax.random.normal(key, shape) * shape[-2] ** -0.5
    if name in ("conv_w", "conv_b"):
        b = TINY["d_conv"] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -b, b)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1., 16.))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))        # inverse softplus
    assert name in ("w", "D"), name
    return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)


def _weights(mc, seed):
    """Every leaf of the program's parameter tree, by its name."""
    from repro.models import model as M
    shapes = jax.eval_shape(lambda k: M.init(mc, k), jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.key(seed)
    leaves = [_leaf(jax.random.fold_in(key, i), str(path[-1].key),
                    s.shape).astype(s.dtype)
              for i, (path, s) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _reference_view(params):
    """The program's parameter tree in the reference's terms (the same
    arrays; nothing is computed)."""
    unit = params["unit"][0]
    mix = unit["mamba"]
    layers = {k: mix[k] for k in ("w_in", "conv_w", "conv_b", "A_log", "D",
                                  "dt_bias", "w_out")}
    layers["ln"] = unit["ln"]["w"]
    layers["gn"] = mix["gn"]["w"]
    return {"emb": params["embed"]["emb"], "norm_f": params["final_norm"]["w"],
            "layers": layers}


@pytest.fixture(scope="module")
def tiny():
    """Program config, program parameters, the reference's view of them,
    and token rows."""
    mc = _program_config()
    params = _weights(mc, 2**31 + 3)
    toks = np.asarray(jax.random.randint(jax.random.key(4), (2, 24), 0,
                                         TINY["vocab_size"]))
    return mc, params, _reference_view(params), toks


def _ref_logits(w, toks, **kw):
    x = ref.hidden(w, toks, TINY, **kw)
    pos = np.broadcast_to(np.arange(toks.shape[1]), toks.shape)
    return ref.logits_at(w, x, pos, TINY["vocab_size"],
                         eps=kw.get("eps", 1e-5))


def test_reference_is_causal_prefill_then_decode(tiny):
    """Logits at a position do not depend on later tokens: the first 16
    positions of a 24-token row equal those of its 16-token prefix, and
    each later position equals a run over its own prefix."""
    _, _, w, toks = tiny
    full = _ref_logits(w, toks)
    np.testing.assert_allclose(_ref_logits(w, toks[:, :16]), full[:, :16],
                               rtol=1e-5, atol=1e-5)
    for t in (17, 20):
        np.testing.assert_allclose(_ref_logits(w, toks[:, :t])[:, -1],
                                   full[:, t - 1], rtol=1e-5, atol=1e-5)


def test_reference_matches_published_mamba2(tiny):
    """The same weights in ``transformers``' Mamba2 (its plain PyTorch
    path, the published block with the SiLU-gated norm)."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    _, _, w, toks = tiny
    conf = tf.Mamba2Config(
        vocab_size=100, hidden_size=64, state_size=16, num_hidden_layers=2,
        layer_norm_epsilon=1e-5, expand=2, conv_kernel=4, n_groups=1,
        head_dim=16, num_heads=8, chunk_size=8, use_bias=False,
        use_conv_bias=True, residual_in_fp32=True, rms_norm=True,
        tie_word_embeddings=True, time_step_limit=(0.0, float("inf")))
    model = tf.Mamba2ForCausalLM(conf).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))  # noqa: E731
    L = w["layers"]
    with torch.no_grad():
        model.backbone.embeddings.weight.copy_(t(w["emb"][:100]))
        model.backbone.norm_f.weight.copy_(t(w["norm_f"]))
        for i, blk in enumerate(model.backbone.layers):
            mx = blk.mixer
            blk.norm.weight.copy_(t(L["ln"][i]))
            mx.in_proj.weight.copy_(t(L["w_in"][i]).T)
            mx.conv1d.weight.copy_(t(L["conv_w"][i]).T[:, None, :])
            mx.conv1d.bias.copy_(t(L["conv_b"][i]))
            mx.dt_bias.copy_(t(L["dt_bias"][i]))
            mx.A_log.copy_(t(L["A_log"][i]))
            mx.D.copy_(t(L["D"][i]))
            mx.norm.weight.copy_(t(L["gn"][i]))
            mx.out_proj.weight.copy_(t(L["w_out"][i]).T)
        got = model(torch.tensor(toks)).logits.numpy()
    want = _ref_logits(w, toks)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_program_departs_only_by_its_gate(tiny):
    """The program's Mamba2 (vector tier, float32) gates its norm with
    sigmoid(z) and takes eps 1e-6: the reference computed so agrees with
    it to float rounding, and the published block does not."""
    from repro.core.registry import REGISTRY
    from repro.models import model as M
    mc, params, w, toks = tiny
    with REGISTRY.use_policy("vector"):
        got, _, _ = M.forward(params, mc, {"tokens": jnp.asarray(toks)},
                              mode="train")
    got = np.asarray(got)[..., :100]
    scale = np.abs(got).max()
    as_program = _ref_logits(w, toks, gate="sigmoid", eps=1e-6)
    np.testing.assert_allclose(got, as_program, rtol=1e-4,
                               atol=1e-4 * scale)
    published = _ref_logits(w, toks)
    assert np.abs(got - published).max() > 0.1 * scale
