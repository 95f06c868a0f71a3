"""Serving-tier tests: batched engine correctness, the bucketed
executable bound, and the process-wide CompiledKernel cache semantics
(including the resolved-target keying regression)."""
import dataclasses
import os
import sys

import numpy as np
import pytest

from repro import port
from repro.core import targets
from repro.serve import BucketPolicy, PortEngine, Request

CORPUS = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                      "examples", "neon_corpus"))
sys.path.insert(0, CORPUS)

import harness  # noqa: E402


@pytest.fixture(scope="module")
def kernels():
    return {name: port.compile_file(os.path.join(CORPUS, fname),
                                    name=name)
            for name, fname in (("xnn_f32_vadd_ukernel", "vadd.c"),
                                ("xnn_f32_vdot_ukernel", "vdot.c"),
                                ("qs8_vmlal_dot_ukernel",
                                 "vmlal_dot.c"))}


def _requests(kernels, rng, ns, target=None):
    reqs = []
    for kname, n in ns:
        k = kernels[kname]
        if kname == "qs8_vmlal_dot_ukernel":
            a = rng.integers(-2, 3, n).astype(np.int8)
            b = rng.integers(-2, 3, n).astype(np.int8)
            out = np.zeros(1, np.int16)
        elif kname == "xnn_f32_vdot_ukernel":
            a = rng.standard_normal(n).astype(np.float32)
            b = rng.standard_normal(n).astype(np.float32)
            out = np.zeros(1, np.float32)
        else:
            a = rng.standard_normal(n).astype(np.float32)
            b = rng.standard_normal(n).astype(np.float32)
            out = np.zeros(n, np.float32)
        reqs.append(Request(k, (n, a, b, out), target=target))
    return reqs


# ---------------------------------------------------------------------------
# engine correctness
# ---------------------------------------------------------------------------

def test_submit_matches_direct_calls(kernels):
    """A mixed slate (three kernels, tails of every shape) must return
    exactly what calling each compiled kernel directly returns, in
    request order."""
    rng = np.random.default_rng(0)
    ns = [("xnn_f32_vadd_ukernel", n) for n in (1, 3, 4, 5, 63, 64, 65)]
    ns += [("xnn_f32_vdot_ukernel", n) for n in (2, 7, 33)]
    ns += [("qs8_vmlal_dot_ukernel", n) for n in (1, 8, 40)]
    reqs = _requests(kernels, rng, ns)
    eng = PortEngine(target="rvv-128", max_batch=8)
    results = eng.submit(reqs)
    assert len(results) == len(reqs)
    for req, got in zip(reqs, results):
        want = np.asarray(req.kernel.compile(target="rvv-128")(*req.args))
        got = np.asarray(got)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_mixed_target_fleet_routes_per_request(kernels):
    """rvv-128 and rvv-1024 requests batch side by side in one submit,
    each against its own target's executable."""
    rng = np.random.default_rng(1)
    wide = _requests(kernels, rng, [("xnn_f32_vadd_ukernel", 40)] * 3,
                     target="rvv-1024")
    narrow = _requests(kernels, rng, [("xnn_f32_vadd_ukernel", 40)] * 3,
                       target="rvv-128")
    eng = PortEngine(target="rvv-128", max_batch=4)
    interleaved = [wide[0], narrow[0], wide[1], narrow[1], wide[2],
                   narrow[2]]
    results = eng.submit(interleaved)
    for req, got in zip(interleaved, results):
        want = np.asarray(
            req.kernel.compile(target=req.target)(*req.args))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    # two groups (one per target), each one chunk of max_batch=4
    st = eng.stats()
    assert st["batches"] == 2
    assert st["inert_rows"] == 2          # 3 real rows per 4-row chunk


def test_oversize_buffer_promotes_bucket(kernels):
    """A caller handing a buffer longer than n * stride must not have
    its untouched tail truncated: the bucket promotes to hold it."""
    k = kernels["xnn_f32_vadd_ukernel"]
    n = 4
    a = np.arange(200, dtype=np.float32)
    b = np.ones(200, np.float32)
    y = np.full(200, -7.0, np.float32)
    eng = PortEngine(target="rvv-128", max_batch=2)
    got = np.asarray(eng.submit([Request(k, (n, a, b, y))])[0])
    want = np.asarray(k.compile(target="rvv-128")(n, a, b, y))
    assert got.shape == (200,)
    np.testing.assert_allclose(got, want)


def test_chunking_splits_groups_at_max_batch(kernels):
    """A group larger than max_batch splits into full-size padded
    chunks; results still line up with request order."""
    rng = np.random.default_rng(2)
    reqs = _requests(kernels, rng, [("xnn_f32_vdot_ukernel", 17)] * 5)
    eng = PortEngine(target="rvv-128", max_batch=2)
    results = eng.submit(reqs)
    st = eng.stats()
    assert st["batches"] == 3 and st["inert_rows"] == 1
    for req, got in zip(reqs, results):
        want = np.asarray(req.kernel.compile(target="rvv-128")(*req.args))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                   atol=1e-6)


def test_bad_arity_raises(kernels):
    eng = PortEngine(target="rvv-128")
    with pytest.raises(ValueError, match="takes 4 args"):
        eng.submit([Request(kernels["xnn_f32_vadd_ukernel"], (4,))])


# ---------------------------------------------------------------------------
# bucketing + the executable bound
# ---------------------------------------------------------------------------

def test_bucket_policy_geometry():
    fine = BucketPolicy.preset("fine")
    coarse = BucketPolicy.preset("coarse")
    assert [fine.bucket(n) for n in (0, 1, 64, 65, 128, 129)] == \
        [64, 64, 64, 128, 128, 256]
    assert [coarse.bucket(n) for n in (1, 64, 65, 256, 257)] == \
        [64, 64, 256, 256, 1024]
    with pytest.raises(KeyError, match="unknown bucket policy"):
        BucketPolicy.preset("nope")


def test_batch_programs_bounded_by_buckets(kernels):
    """Free-form lengths across two buckets and two targets demand at
    most buckets x targets x kernels executables — resubmitting new
    lengths inside the same buckets adds none."""
    rng = np.random.default_rng(3)
    eng = PortEngine(target="rvv-128", max_batch=4, bucket_policy="fine")
    names = ("xnn_f32_vadd_ukernel", "qs8_vmlal_dot_ukernel")
    for tgt in ("rvv-128", "rvv-1024"):
        for _ in range(2):
            ns = [(nm, int(rng.integers(8, 60))) for nm in names]
            ns += [(nm, int(rng.integers(70, 120))) for nm in names]
            eng.submit(_requests(kernels, rng, ns, target=tgt))
    st = eng.stats()
    bound = 2 * 2 * 2                      # buckets x targets x kernels
    assert st["batch_programs"] <= bound, st
    before = st["batch_programs"]
    # fresh lengths, same buckets: no new executables
    ns = [(nm, int(rng.integers(8, 60))) for nm in names]
    eng.submit(_requests(kernels, rng, ns, target="rvv-128"))
    assert eng.stats()["batch_programs"] == before


def test_warmup_populates_compile_cache(kernels):
    eng = PortEngine(target="rvv-128")
    before = port.compiled_cache_info()
    stats = eng.warmup(kernels, targets=["rvv-128", "rvv-1024"])
    assert stats == {"kernels": 3, "targets": 2, "compiles": 6}
    after = port.compiled_cache_info()
    # every (kernel, target) now resident: warming again is all hits
    eng.warmup(kernels, targets=["rvv-128", "rvv-1024"])
    again = port.compiled_cache_info()
    assert again["misses"] == after["misses"]
    assert again["hits"] >= after["hits"] + 6
    assert after["misses"] >= before["misses"]


# ---------------------------------------------------------------------------
# the process-wide CompiledKernel cache
# ---------------------------------------------------------------------------

def test_compile_cache_keys_on_resolved_target(kernels):
    """Regression (satellite 2): ``compile()`` under two different
    ``use_target`` scopes must pin two different executables — the old
    per-kernel dict keyed the ``None`` sentinel's *name* and aliased
    them."""
    k = kernels["xnn_f32_vadd_ukernel"]
    with targets.use_target("rvv-128"):
        narrow = k.compile()
    with targets.use_target("rvv-1024"):
        wide = k.compile()
    assert narrow is not wide
    assert narrow.target.name == "rvv-128"
    assert wide.target.name == "rvv-1024"
    # and the explicit spelling resolves to the same cache entry
    assert k.compile(target="rvv-128") is narrow


def test_compile_cache_keys_on_target_value(kernels):
    """An ad-hoc Target sharing a registered name gets its own entry
    (value keying, mirroring the selection LRU)."""
    k = kernels["xnn_f32_vadd_ukernel"]
    registered = k.compile(target="rvv-128")
    adhoc = dataclasses.replace(targets.get_target("rvv-128"), vlen=256)
    compiled = k.compile(target=adhoc)
    assert compiled is not registered
    assert compiled.target.vlen == 256
    assert k.compile(target=adhoc) is compiled


def test_compile_cache_bounded_eviction(kernels):
    """Capacity is enforced LRU-first, counters track it, and an
    evicted entry recompiles on demand (holders keep working)."""
    k = kernels["xnn_f32_vdot_ukernel"]
    info = port.compiled_cache_info()
    try:
        port.set_compiled_cache_capacity(2)
        c64 = k.compile(target="rvv-64")
        k.compile(target="rvv-256")
        k.compile(target="rvv-512")        # evicts rvv-64
        info2 = port.compiled_cache_info()
        assert info2["capacity"] == 2 and info2["size"] == 2
        assert info2["evictions"] >= 1
        again = k.compile(target="rvv-64") # recompiled, new object
        assert again is not c64
        # the evicted handle still executes
        a = np.ones(5, np.float32)
        np.testing.assert_allclose(
            np.asarray(c64(5, a, a, np.zeros(1, np.float32))),
            np.asarray(again(5, a, a, np.zeros(1, np.float32))))
    finally:
        port.set_compiled_cache_capacity(
            max(info["capacity"], port._CompiledKernelCache
                .DEFAULT_CAPACITY))

    with pytest.raises(ValueError, match="capacity must be >= 1"):
        port.set_compiled_cache_capacity(0)


def test_compile_cache_info_counts(kernels):
    port.compiled_cache_clear()
    k = kernels["qs8_vmlal_dot_ukernel"]
    assert port.compiled_cache_info()["size"] == 0
    k.compile(target="rvv-128")
    k.compile(target="rvv-128")
    info = port.compiled_cache_info()
    assert info["misses"] == 1 and info["hits"] == 1
    assert info["size"] == 1


# ---------------------------------------------------------------------------
# spans, program names and transfer counters
# ---------------------------------------------------------------------------

CHUNK_STAGES = ["port.pad", "port.h2d", "port.launch", "port.fetch",
                "port.slice"]


def _mixed_slate(kernels):
    """Four groups in five chunks at max_batch=4: five vadd rows in the
    64 bucket (a chunk of 4 and one of 1), one in the 128 bucket, one
    vdot and one qs8 dot; 8 live rows."""
    ns = [("xnn_f32_vadd_ukernel", n) for n in (1, 3, 5, 7, 9)]
    ns += [("xnn_f32_vadd_ukernel", 70), ("xnn_f32_vdot_ukernel", 7),
           ("qs8_vmlal_dot_ukernel", 3)]
    return _requests(kernels, np.random.default_rng(5), ns)


def _port_spans(log_dir):
    """Every ``port.*`` event of the trace under ``log_dir``, in start
    order: (name, start_ns, end_ns, args)."""
    import glob

    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    prof = ProfileData.from_file(path)
    evs = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
           for plane in prof.planes for line in plane.lines
           for ev in line.events if ev.name.startswith("port.")]
    return sorted(evs, key=lambda ev: (ev[1], -ev[2]))


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_submit_spans_nest_with_args(kernels, tmp_path):
    """Under the profiler, each slate records port.submit > port.plan and
    one port.chunk per chunk > pad, h2d, launch, fetch, slice in order,
    with the slate's number, the chunk's live rows and new_program set
    on the first sight of a shape only."""
    import jax
    reqs = _mixed_slate(kernels)
    eng = PortEngine(target="rvv-128", max_batch=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit(reqs)
        eng.submit(reqs)
    finally:
        jax.profiler.stop_trace()
    evs = _port_spans(str(tmp_path))
    submits = [e for e in evs if e[0] == "port.submit"]
    assert [s[3] for s in submits] == [
        {"slate": 1, "requests": 8, "groups": 4},
        {"slate": 2, "requests": 8, "groups": 4}]
    launches_new = []
    for sub in submits:
        inner = [e for e in evs if e is not sub and _inside(e, sub)]
        plans = [e for e in inner if e[0] == "port.plan"]
        chunks = [e for e in inner if e[0] == "port.chunk"]
        assert len(plans) == 1 and len(chunks) == 5
        assert plans[0][2] <= chunks[0][1]
        assert sum(c[3]["rows"] for c in chunks) == 8
        assert sorted(c[3]["rows"] for c in chunks) == [1, 1, 1, 1, 4]
        for c in chunks:
            assert c[3]["slate"] == sub[3]["slate"]
            assert c[3]["target"] == "rvv-128"
            assert c[3]["bucket"] in (64, 128)
            assert c[3]["kernel"] in kernels
            stages = [e for e in inner if _inside(e, c) and e is not c]
            assert [e[0] for e in stages] == CHUNK_STAGES
            assert all(a[2] <= b[1] for a, b in zip(stages, stages[1:]))
            launches_new.append(stages[2][3]["new_program"])
    # slate 1 sees four shapes first; its second vadd@64 chunk and all of
    # slate 2 reuse them
    assert launches_new[:5].count(1) == 4 and launches_new[5:] == [0] * 5
    assert not any(e[0] == "port.fallback" for e in evs)


def test_fallback_span_counts_rows(kernels, tmp_path):
    """A planted batch fault serves its chunk row by row inside
    port.fallback, nested in the chunk, after the failed launch."""
    import jax

    from repro.port import faultinject as fi
    from repro.port import resilience as rz
    reqs = _requests(kernels, np.random.default_rng(6),
                     [("xnn_f32_vdot_ukernel", n) for n in (3, 9, 17)])
    eng = PortEngine(target="rvv-128", max_batch=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with fi.injected("engine.batch", error=rz.ExecError, times=None):
            res = eng.submit(reqs)
    finally:
        jax.profiler.stop_trace()
    assert not any(isinstance(r, Exception) for r in res)
    evs = _port_spans(str(tmp_path))
    chunk, = [e for e in evs if e[0] == "port.chunk"]
    fb, = [e for e in evs if e[0] == "port.fallback"]
    assert fb[3] == {"rows": 3} and _inside(fb, chunk)
    names = [e[0] for e in evs if _inside(e, chunk) and e is not chunk
             and not e[0].startswith("port.fallback")]
    assert names == ["port.pad", "port.h2d", "port.launch"]


@pytest.mark.parametrize("kname,tname,name", [
    ("xnn_f32_vadd_ukernel", "rvv-128", "port_xnn_f32_vadd_ukernel_rvv_128"),
    ("qs8_vmlal_dot_ukernel", "rvv-1024",
     "port_qs8_vmlal_dot_ukernel_rvv_1024")])
def test_batch_program_named_per_kernel_and_target(kernels, kname, tname,
                                                   name):
    """The batched program carries ``port_<kernel>_<target>``, so the
    trace names it ``jit_port_<kernel>_<target>``."""
    import jax
    k = kernels[kname]
    req = _requests(kernels, np.random.default_rng(7), [(kname, 9)],
                    target=tname)[0]
    eng = PortEngine(max_batch=4)
    _, tgt, lens = eng._plan(req)
    shapes = [jax.ShapeDtypeStruct((4,), np.int32) if n is None else
              jax.ShapeDtypeStruct((4, n), np.asarray(a).dtype)
              for a, n in zip(req.args, lens)]
    text = eng._program(k, tgt).lower(*shapes).as_text()
    assert f"@jit_{name} " in text


def test_transfer_counters_match_hand_count(kernels):
    """h2d_bytes counts every column sent (inert rows and the scalar
    vector included), d2h_bytes every output column fetched."""
    import jax
    rng = np.random.default_rng(8)
    reqs = _requests(kernels, rng, [("xnn_f32_vadd_ukernel", 5),
                                    ("xnn_f32_vadd_ukernel", 70),
                                    ("xnn_f32_vdot_ukernel", 7)])
    eng = PortEngine(target="rvv-128", max_batch=4)
    eng.submit(reqs)
    st = eng.stats()
    n_bytes = jax.dtypes.canonicalize_dtype(np.int64).itemsize
    f32 = 4
    # vadd@64: a, b, y (4, 64) f32 + n (4,); vadd@128: (4, 128);
    # vdot@64: a, b (4, 64) f32, sum (4, 1) f32 + n (4,)
    h2d = (3 * 4 * 64 * f32 + 4 * n_bytes) + (3 * 4 * 128 * f32
                                             + 4 * n_bytes) \
        + (2 * 4 * 64 * f32 + 4 * 1 * f32 + 4 * n_bytes)
    d2h = 4 * 64 * f32 + 4 * 128 * f32 + 4 * 1 * f32
    assert (st["h2d_bytes"], st["d2h_bytes"]) == (h2d, d2h)


def test_padding_counters_on_a_fixed_slate(kernels):
    """payload_elems and padded_elems, bumped once per chunk, give the
    totals of one bump per row and output: 77 requested output elements
    (5 + 70 + 1 + 1) padded to 194 (64 + 128 + 1 + 1)."""
    rng = np.random.default_rng(9)
    reqs = _requests(kernels, rng, [("xnn_f32_vadd_ukernel", 5),
                                    ("xnn_f32_vadd_ukernel", 70),
                                    ("xnn_f32_vdot_ukernel", 7),
                                    ("qs8_vmlal_dot_ukernel", 3)])
    eng = PortEngine(target="rvv-128", max_batch=4)
    eng.submit(reqs)
    eng.submit(reqs[:1])
    st = eng.stats()
    assert (st["payload_elems"], st["padded_elems"]) == (77 + 5, 194 + 64)
    assert st["pad_overhead"] == (194 + 64) / (77 + 5) - 1.0


# ---------------------------------------------------------------------------
# chip-width strips: the batched program's first rung runs each strip at
# the executing chip's register tile
# ---------------------------------------------------------------------------

# the benchmark's eight corpus kernels, with the element type of the
# narrowest register in each strip (it sets the tile)
CHIP_KERNELS = [("vadd.c", "xnn_f32_vadd_ukernel", np.float32),
                ("vmul.c", "xnn_f32_vmul_ukernel", np.float32),
                ("vmull_requant.c", "qs8_vmul_requant_ukernel", np.int8),
                ("vclamp.c", "xnn_f32_vclamp_ukernel", np.float32),
                ("vmlal_dot.c", "qs8_vmlal_dot_ukernel", np.int8),
                ("vtanh.c", "xnn_f32_vtanh_ukernel", np.float32),
                ("vsigmoid.c", "xnn_f32_vsigmoid_ukernel", np.float32),
                ("vdot.c", "xnn_f32_vdot_ukernel", np.float32)]
LARGEST_BUCKET = 65536
REDUCTION_BUDGET_U = 8.0        # float32 unit roundoffs x sum of |terms|


def _case(kname, n):
    """The harness case of ``kname`` at ``n`` (buffers hold at least one
    element, so the unbatched narrow program can trace n = 0)."""
    m = max(1, n)
    return next(c for c in harness.cases(n=m, tail_n=m)
                if c.kernel == kname)


@pytest.mark.parametrize("target", ["rvv-128", "rvv-1024"])
@pytest.mark.parametrize("fname,kname,dtype", CHIP_KERNELS)
def test_chip_width_program_matches_narrow_and_reference(fname, kname, dtype,
                                                         target, tmp_path):
    """The chip-width program answers n in {0, 1, tile - 1, tile,
    tile + 1, largest bucket}, beside inert rows, as the narrow compiled
    program does (bitwise; the f32 dot within its reduction budget) and
    as the NumPy reference does; it is built on the first rung, and its
    chunks carry the tile as ``strip``."""
    import jax
    k = port.compile_file(os.path.join(CORPUS, fname), name=kname)
    tile = targets.compile_target().vreg_elems(dtype)
    rng = np.random.default_rng(11)
    ns = [0, 1, tile - 1, tile, tile + 1, LARGEST_BUCKET]
    reqs, cases = [], []
    for n in ns:
        case = _case(kname, n)
        args = list(case.make_args(rng))
        args[0] = n
        reqs.append(Request(k, tuple(args), target=target))
        cases.append(case)
    eng = PortEngine(target=target, max_batch=4)
    outs = eng.submit(reqs)
    narrow = k.compile(target=target)
    for req, case, got in zip(reqs, cases, outs):
        n, args = req.args[0], req.args
        want = np.asarray(narrow(*args))
        ref = case.reference(*args)
        if kname == "xnn_f32_vdot_ukernel":
            a, b = (np.asarray(x, np.float64)[:n] for x in args[1:3])
            bound = REDUCTION_BUDGET_U * 2.0 ** -24 * np.abs(a * b).sum()
            for other in (want, ref):
                assert abs(float(got[0]) - float(other[0])) <= bound, n
        else:
            np.testing.assert_array_equal(got, want, err_msg=f"n={n}")
            harness.assert_conforms(got, ref, case, f"{kname} n={n}")
    st = eng.stats()
    assert st["chip_width_programs"] == 1
    assert st["program_fallbacks"] == 0 and st["batch_faults"] == 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit(reqs[1:2])
    finally:
        jax.profiler.stop_trace()
    chunk, = [e for e in _port_spans(str(tmp_path)) if e[0] == "port.chunk"]
    assert chunk[3]["strip"] == tile
