"""Compile the chip's kernels for a described TPU v5e, without a chip.

Interpret mode (every other kernel test) accepts block shapes, slices
and scratch sizes that the TPU compiler refuses.  These tests lower each
Pallas kernel of the main paths with ``interpret=False`` and compile it
for one chip of a described ``v5e:2x2`` topology, at the shapes the
chip runs: the ten XNNPACK conversions at the Figure-2 workload shapes,
flash and decode attention at gemma2-2b serving shapes, flash attention
at deepseek-v2-lite's latent-attention widths and the grouped product of
its dropless MoE, ssd at mamba2-1.3b shapes, the batched program
``PortEngine`` builds for corpus kernels on rvv-1024, and the serving
engine's whole decode step for deepseek-v2-lite at the model cell's
shapes.

The topology is described inside a fixture, never while the module is
imported: only the test worker that runs this file may load the TPU
compiler library.  All such compiles live in this one file.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.registry import REGISTRY
from repro.kernels import figure2, flash_attention, ops, ssd

CORPUS = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                      "examples", "neon_corpus"))
sys.path.insert(0, CORPUS)

import harness  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Registered Pallas lowerings pick interpret mode from the default
    backend, which is the CPU here; compile the real kernels instead."""
    monkeypatch.setattr(ops, "_interp", lambda: False)


def _compile(fn, *shapes):
    txt = jax.jit(fn).lower(*shapes).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in txt
    return txt


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


@pytest.mark.parametrize("name", figure2.FIGURE2_OPS)
def test_xnnpack_kernel_compiles(name, one_chip, on_chip):
    _, op, args, kw = next(w for w in figure2.workloads()
                           if w[0] == name)
    fn, arrs = figure2.array_fn(REGISTRY.lowering(op, "pallas").fn,
                                args, kw)
    _compile(fn, *[_sds(a, one_chip) for a in arrs])


def test_flash_attention_gemma2_prefill(one_chip):
    """gemma2-2b prefill: 8 query heads over 4 kv heads, head_dim 256,
    bf16, local window and logit softcap."""
    q = jax.ShapeDtypeStruct((4, 8, 512, 256), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 4, 512, 256), jnp.bfloat16,
                              sharding=one_chip)
    _compile(lambda q, k, v: flash_attention.flash_attention(
        q, k, v, causal=True, window=4096, softcap=50.0), q, kv, kv)


def test_flash_attention_mla_prefill(one_chip, on_chip):
    """deepseek-v2-lite prefill through the registered lowering: 16 heads,
    query and key head 192 over a value head of 128, which it zero-pads to
    the query's width."""
    q = jax.ShapeDtypeStruct((2, 2048, 16, 192), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 2048, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    fn = REGISTRY.lowering("attention", "pallas").fn
    _compile(lambda q, k, v: fn(q, k, v, True, None, None, 192 ** -0.5),
             q, q, v)


def test_grouped_gemm_moe_block(one_chip):
    """The dropless MoE's grouped product at deepseek-v2-lite widths: one
    block of 16,384 tokens x 6 assignments over 64 experts of 2,048 x
    1,408 lowers to XLA's ragged dot, not a dense product per expert."""
    x = jax.ShapeDtypeStruct((16384 * 6, 2048), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((64, 2048, 1408), jnp.bfloat16,
                             sharding=one_chip)
    gs = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    c = jax.jit(ops.grouped_gemm).lower(x, w, gs).compile()
    assert "ragged-dot" in c.as_text()
    cost = c.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["flops"] < 1.1 * 2 * 16384 * 6 * 2048 * 1408


def test_decode_attention_gemma2(one_chip):
    q = jax.ShapeDtypeStruct((4, 8, 1, 256), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 4, 528, 256), jnp.bfloat16,
                              sharding=one_chip)
    lens = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    _compile(lambda q, k, v, n: flash_attention.decode_attention(
        q, k, v, n, softcap=50.0), q, kv, kv, lens)


def test_ssd_mamba2(one_chip):
    """mamba2-1.3b: 64 heads of 64, state 128, one group, chunk 128."""
    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    _compile(lambda x, dt, A, B, C, D: ssd.ssd(x, dt, A, B, C, D,
                                               chunk=128),
             s((1, 2048, 64, 64), jnp.bfloat16), s((1, 2048, 64)), s((64,)),
             s((1, 2048, 1, 128), jnp.bfloat16),
             s((1, 2048, 1, 128), jnp.bfloat16), s((64,)))


@pytest.mark.parametrize("kernel", ["xnn_f32_vadd_ukernel",
                                    "qs8_gemm_mx8_ukernel"])
def test_port_engine_program_compiles(kernel, one_chip):
    """The jit(vmap(...)) program PortEngine serves a batch with, at the
    padded shapes it builds for an n=4096 request on rvv-1024."""
    from repro import port
    from repro.core import targets
    from repro.serve import PortEngine, Request

    case = next(c for c in harness.cases(n=4096, tail_n=4093)
                if c.kernel == kernel)
    k = port.compile_file(os.path.join(CORPUS, case.file), name=kernel)
    args = case.make_args(np.random.default_rng(0))
    eng = PortEngine(policy="pallas", revec=True)
    _, tgt, lens = eng._plan(Request(k, args, target="rvv-1024"))
    shapes = [jax.ShapeDtypeStruct((eng.max_batch,), jnp.int32,
                                   sharding=one_chip) if n is None else
              jax.ShapeDtypeStruct((eng.max_batch, n),
                                   np.asarray(a).dtype, sharding=one_chip)
              for a, n in zip(args, lens)]
    assert tgt == targets.get_target("rvv-1024")
    eng._program(k, tgt).lower(*shapes).compile()


@pytest.mark.parametrize("kernel,dtype", [("xnn_f32_vmul_ukernel", np.float32),
                                          ("qs8_vmul_requant_ukernel",
                                           np.int8)])
def test_chip_width_program_compiles(kernel, dtype, one_chip):
    """PortEngine's chip-width program (strips at v5e's 8 x 128 f32 or
    32 x 128 int8 tile) for a full batch of 32 rows in the largest
    bucket the benchmark serves, 65,536 elements, on rvv-128."""
    from repro import port
    from repro.core import targets
    from repro.serve import PortEngine, Request

    n = 65536
    case = next(c for c in harness.cases(n=n, tail_n=n)
                if c.kernel == kernel)
    k = port.compile_file(os.path.join(CORPUS, case.file), name=kernel)
    args = case.make_args(np.random.default_rng(0))
    eng = PortEngine(policy="pallas", revec=True, max_batch=32)
    _, tgt, lens = eng._plan(Request(k, args, target="rvv-128"))
    assert lens[1:] == [n, n, n]
    shapes = [jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
              if m is None else
              jax.ShapeDtypeStruct((32, m), np.asarray(a).dtype,
                                   sharding=one_chip)
              for a, m in zip(args, lens)]
    prog = eng._program(k, tgt)
    assert eng._strips[(id(k), tgt)] == \
        targets.get_target("tpu-v5e").vreg_elems(dtype)
    prog.lower(*shapes).compile()


_INSTR = re.compile(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)")


def _materialised(txt):
    """(shape, opcode, fused root opcode) of every array-valued instruction
    of a compiled module outside fusion bodies: what the step writes to
    memory.  A fusion's root is its computation's ROOT instruction."""
    comps, name = {}, None
    for line in txt.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.strip() != "}":
            comps[name].append(line)
    fused = {c for lines in comps.values() for ln in lines
             for c in re.findall(r"calls=%([\w.\-]+)", ln)
             if re.search(r" fusion\(.*kind=k", ln)}
    roots = {n: _INSTR.match(next(ln for ln in lines if "ROOT" in ln))
             for n, lines in comps.items() if n in fused}
    out = []
    for n, lines in comps.items():
        if n in fused:
            continue
        for ln in lines:
            m = _INSTR.match(ln)
            if not m:
                continue
            root = None
            if m.group(2) == "fusion":
                body = re.search(r"calls=%([\w.\-]+)", ln).group(1)
                root = roots[body] and roots[body].group(2)
            out.append((tuple(int(d) for d in m.group(1).split(",") if d),
                        m.group(2), root))
    return out


def test_deepseek_decode_step_writes_cache_in_place(one_chip, on_chip):
    """The engine's decode step (``make_serve_step``, cache donated as
    ``Engine`` does) for deepseek-v2-lite's first 7 layers at batch 32 and
    2,304 positions: no copy, slice or update materialises a layer's
    latent cache or the stack of them (the scan once sliced each layer's
    out, wrote it back whole and copied the stack, 663,609,856 bytes of
    temporaries); the one write is the scatter of the new entries into
    the donated cache.  Lowered under the chip's default policy."""
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve.engine import _new_counts, make_serve_step

    cfg = get_config("deepseek-v2-lite-16b").replace(n_layers=7)
    b, s = 32, 2304

    def sds(tree):
        return jax.tree.map(lambda x: _sds(x, one_chip), tree)

    params = sds(jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0))))
    cache = sds(jax.eval_shape(lambda: M.init_cache(cfg, b, s)))
    acc = sds(jax.eval_shape(lambda: _new_counts(cfg)))
    tok, lens, live = (jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
                       for shape, dt in (((b, 1), jnp.int32),
                                         ((b,), jnp.int32),
                                         ((b,), jnp.bool_)))
    with REGISTRY.use_policy("pallas"):
        c = jax.jit(make_serve_step(cfg), donate_argnums=(1, 5)).lower(
            params, cache, tok, lens, live, acc).compile()
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < 128 * 2 ** 20
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    widths = {x.shape[-1] for x in jax.tree.leaves(cache)} | {
        cfg.kv_lora_rank, cfg.qk_rope_dim}
    cached = [(shape, op, root) for shape, op, root in _materialised(
        c.as_text()) if len(shape) >= 2 and shape[-2] == s and
        shape[-1] in widths]
    in_place = [x for x in cached if "scatter" in (x[1], x[2])]
    assert len(in_place) == 2          # the lone dense layer's, the stack's
    assert [x for x in cached if x not in in_place and
            x[1] not in ("parameter", "get-tuple-element", "bitcast")] == []
