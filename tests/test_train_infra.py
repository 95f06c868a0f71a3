"""Training infra: loss goes down, grad accumulation, checkpoint/restart,
watchdog, compression, data determinism."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpointer as ckpt
from repro.configs import get_config
from repro.data.pipeline import SyntheticLM
from repro.models import model as M
from repro.optim import adamw, compression
from repro.runtime.fault_tolerance import (FailureInjector, Supervisor,
                                           Watchdog)
from repro.train.loop import TrainConfig, make_train_step, train


def test_loss_decreases():
    cfg = get_config("gemma2-2b").reduced()
    res = train(cfg, steps=20, batch_size=4, seq_len=32, log_every=1000)
    losses = [h["loss"] for h in res["history"]]
    assert losses[-1] < losses[0]


def test_grad_accum_equivalent():
    """accum=2 must match accum=1 on the same global batch (fp32)."""
    cfg = get_config("mistral-large-123b").reduced().replace(dtype="float32")
    key = jax.random.PRNGKey(0)
    params = M.init(cfg, key)
    opt = adamw.init(params)
    data = SyntheticLM(cfg.vocab_size, 32, 4)
    batch = data.batch(0)
    outs = []
    for accum in (1, 2):
        step = jax.jit(make_train_step(cfg, TrainConfig(accum=accum)))
        p2, _, _, m = step(params, opt, None, batch)
        outs.append((p2, float(m["loss"])))
    np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(outs[0][0]), jax.tree.leaves(outs[1][0])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)


def test_checkpoint_roundtrip_and_atomicity():
    cfg = get_config("gemma2-2b").reduced()
    params = M.init(cfg, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        path = ckpt.save(d, 3, {"params": params})
        assert path.endswith("step_00000003")
        assert ckpt.latest_step(d) == 3
        # no .tmp residue (atomic rename)
        assert not [f for f in os.listdir(d) if f.endswith(".tmp")]
        loaded = ckpt.restore(d, 3, {"params": params})
        for a, b in zip(jax.tree.leaves(loaded["params"]),
                        jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_async_checkpointer_gc():
    with tempfile.TemporaryDirectory() as d:
        saver = ckpt.AsyncCheckpointer(d, keep=2)
        tree = {"x": jnp.arange(10)}
        for s in range(5):
            saver.save(s, tree)
        saver.wait()
        assert ckpt.list_steps(d) == [3, 4]


def test_restart_resumes_from_checkpoint():
    cfg = get_config("gemma2-2b").reduced()
    with tempfile.TemporaryDirectory() as d:
        inj = FailureInjector(fail_at=[7])
        res = train(cfg, steps=10, batch_size=2, seq_len=16, ckpt_dir=d,
                    ckpt_every=3, injector=inj, log_every=1000)
        assert res["restarts"] == 1
        steps_seen = [h["step"] for h in res["history"]]
        assert steps_seen[-1] == 9
        assert ckpt.latest_step(d) == 9


def test_supervisor_gives_up():
    sup = Supervisor(max_restarts=2, backoff=0.0)
    calls = []

    def body(start):
        calls.append(start)
        raise RuntimeError("persistent failure")

    with pytest.raises(RuntimeError):
        sup.run(body, lambda: 0)
    assert len(calls) == 3  # initial + 2 restarts


def test_watchdog_flags_straggler():
    import time
    w = Watchdog(threshold=3.0, window=16)
    for s in range(10):
        w.start()
        time.sleep(0.002)
        w.stop(s)
    w.start()
    time.sleep(0.05)
    assert w.stop(10) is True
    assert len(w.incidents) == 1


def test_compression_error_feedback():
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=256) * 1e-3,
                          jnp.float32)}
    err = compression.err_init(g)
    packed, err = compression.compress(g, err)
    deq = compression.decompress(packed)
    # error feedback: residual carried, not lost
    total = deq["w"] + err["w"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(g["w"]),
                               rtol=1e-6, atol=1e-7)
    assert packed["q"]["w"].dtype == jnp.int8


def test_compressed_training_still_learns():
    cfg = get_config("gemma2-2b").reduced()
    res = train(cfg, steps=15, batch_size=4, seq_len=32,
                tcfg=TrainConfig(compress_grads=True), log_every=1000)
    losses = [h["loss"] for h in res["history"]]
    assert losses[-1] < losses[0]


def test_data_determinism_and_host_sharding():
    d = SyntheticLM(1000, 64, 8, seed=1)
    b1 = d.batch(5)
    b2 = d.batch(5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    # targets are next-token shifted
    np.testing.assert_array_equal(np.asarray(b1["tokens"][:, 1:]),
                                  np.asarray(b1["targets"][:, :-1]))
    # host shards tile the global batch
    h0 = d.host_batch(5, 0, 2)
    h1 = d.host_batch(5, 1, 2)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(h0["tokens"]), np.asarray(h1["tokens"])]),
        np.asarray(b1["tokens"]))


def test_elastic_reshard_on_load():
    """Checkpoint saved under one layout restores under another mesh."""
    cfg = get_config("gemma2-2b").reduced()
    params = M.init(cfg, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 0, {"params": params})
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        from repro.models import sharding as Sh
        specs = Sh.param_pspecs(params, cfg, mesh)
        shardings = Sh.ns(mesh, specs)
        loaded = ckpt.restore(d, 0, {"params": params},
                              shardings={"params": shardings})
        leaf = jax.tree.leaves(loaded["params"])[0]
        assert hasattr(leaf, "sharding")


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is left to JAX: the helper reports it
    and sets no path of its own."""
    from repro.launch import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo(monkeypatch):
    """Without the variable the cache sits at a fixed ``<repo>/.jax_cache``
    (the path is part of the cache key)."""
    from repro.launch import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        got = enable_compile_cache()
        assert got == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert enable_compile_cache() == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
