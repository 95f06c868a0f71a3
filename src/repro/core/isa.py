"""The portable logical vector ISA (NEON semantics, tile granularity).

Each op mirrors a NEON intrinsic family from the paper and registers up to
three lowerings in the conversion ladder (see registry.py):

  generic — scalar-semantics emulation (the auto-vectorized-loop tier, and
            the correctness oracle),
  vector  — whole-array jnp (the vector-attribute tier; the paper keeps
            this tier for simple arithmetic — Listing 8 — because it
            already produces optimal code),
  pallas/customized — only where the generic lowering is structurally bad,
            mirroring the paper's customized conversions:
              vget_high -> slidedown          (Listing 5)
              vceq      -> mv+mseq+merge      (Listing 6)
              vrbit     -> binary magic numbers (Listing 7)

Ops take/return plain jnp arrays: a "register" is a logical tile of any
shape (vtypes.LVec); models call these at tensor granularity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register, dispatch
from .trace import scalar_cost, vector_cost

__all__ = [
    "vadd", "vsub", "vmul", "vmax", "vmin", "vabs", "vneg", "vand", "vorr",
    "veor", "vshl_n", "vshr_n", "vceq", "vcgt", "vcge", "vclt", "vcle",
    "vbsl", "vmla", "vmls", "vfma", "vget_high", "vget_low", "vcombine",
    "vext", "vrev64", "vrbit", "vdup", "vpadd", "vaddv", "vmaxv", "vminv",
    "vrecpe", "vrecps", "vrsqrte", "vrsqrts", "vcvt", "vzip", "vtbl",
    "vld1", "vst1", "vld1m", "vst1m", "vtile", "vqadd", "vqsub",
    "vreinterpret", "vmull", "vaddl", "vsubl", "vmlal", "vmlsl",
    "vmovl", "vmovn", "vqmovn", "vqmovun", "vld2", "vst2", "vld2m",
    "vst2m", "vld3", "vst3", "vld3m", "vst3m", "vld4", "vst4",
    "vld4m", "vst4m", "vld1g", "vld1gm", "vfold",
]


def _binary(op_name, jnp_fn, scalar_emu=None):
    """Register generic+vector lowerings for a simple binary op.

    Like the paper (Listing 8), simple arithmetic keeps the vector tier as
    its best lowering — a customized kernel cannot beat one VPU op.
    """
    emu = scalar_emu or jnp_fn

    @register(op_name, "generic", cost=scalar_cost(),
              doc="scalar-loop emulation")
    def _g(a, b):
        flat_a, flat_b = jnp.ravel(a), jnp.ravel(jnp.broadcast_to(b, jnp.shape(a)))
        out = jax.vmap(lambda x, y: emu(x, y))(flat_a, flat_b)
        return out.reshape(jnp.shape(a))

    @register(op_name, "vector", cost=vector_cost(),
              doc="vector-attribute analogue (jnp whole-array)")
    def _v(a, b):
        return jnp_fn(a, b)

    def api(a, b):
        return dispatch(op_name, a, b)

    api.__name__ = op_name
    return api


vadd = _binary("vadd", jnp.add)
vsub = _binary("vsub", jnp.subtract)
vmul = _binary("vmul", jnp.multiply)
vmax = _binary("vmax", jnp.maximum)
vmin = _binary("vmin", jnp.minimum)
vand = _binary("vand", jnp.bitwise_and)
vorr = _binary("vorr", jnp.bitwise_or)
veor = _binary("veor", jnp.bitwise_xor)


def _unary(op_name, jnp_fn):
    @register(op_name, "generic", cost=scalar_cost())
    def _g(a):
        return jax.vmap(jnp_fn)(jnp.ravel(a)).reshape(jnp.shape(a))

    @register(op_name, "vector", cost=vector_cost())
    def _v(a):
        return jnp_fn(a)

    def api(a):
        return dispatch(op_name, a)

    api.__name__ = op_name
    return api


vabs = _unary("vabs", jnp.abs)
vneg = _unary("vneg", jnp.negative)


# -- shifts (immediate) ------------------------------------------------------

@register("vshl_n", "vector", cost=vector_cost())
def _vshl_v(a, n):
    return jnp.left_shift(a, n)


@register("vshl_n", "generic", cost=scalar_cost())
def _vshl_g(a, n):
    return jax.vmap(lambda x: jnp.left_shift(x, n))(jnp.ravel(a)).reshape(a.shape)


def vshl_n(a, n):
    return dispatch("vshl_n", a, n)


@register("vshr_n", "vector", cost=vector_cost())
def _vshr_v(a, n):
    return jnp.right_shift(a, n)


@register("vshr_n", "generic", cost=scalar_cost())
def _vshr_g(a, n):
    return jax.vmap(lambda x: jnp.right_shift(x, n))(jnp.ravel(a)).reshape(a.shape)


def vshr_n(a, n):
    return dispatch("vshr_n", a, n)


# -- compares: NEON returns all-ones/all-zeros lanes of the *unsigned* type --

def _umask_dtype(dtype):
    return jnp.dtype(f"uint{jnp.dtype(dtype).itemsize * 8}")


def _cmp(op_name, jnp_cmp):
    @register(op_name, "generic", cost=scalar_cost(3))
    def _g(a, b):
        udt = _umask_dtype(a.dtype)
        out = jax.vmap(lambda x, y: jnp.where(jnp_cmp(x, y),
                                              jnp.array(~np.uint64(0)).astype(udt),
                                              jnp.zeros((), udt)))(
            jnp.ravel(a), jnp.ravel(jnp.broadcast_to(b, a.shape)))
        return out.reshape(a.shape)

    # Customized lowering, mirroring Listing 6 (vmv + vmseq + vmerge):
    # build the zero register, compare to a mask, merge -1 under the mask.
    @register(op_name, "pallas", cost=vector_cost(3),
              doc="mv+mseq+merge composition (paper Listing 6)")
    def _c(a, b):
        udt = _umask_dtype(a.dtype)
        vs_0 = jnp.zeros(a.shape, udt)                  # vmv.v.x
        mask = jnp_cmp(a, b)                            # vmseq.vv
        return jnp.where(mask, jnp.array(~np.uint64(0)).astype(udt), vs_0)  # vmerge

    def api(a, b):
        return dispatch(op_name, a, b)

    api.__name__ = op_name
    return api


vceq = _cmp("vceq", jnp.equal)
vcgt = _cmp("vcgt", jnp.greater)
vcge = _cmp("vcge", jnp.greater_equal)
vclt = _cmp("vclt", jnp.less)
vcle = _cmp("vcle", jnp.less_equal)


# -- select / fused ops ------------------------------------------------------

@register("vbsl", "vector", cost=vector_cost(3))
def _vbsl_v(mask, a, b):
    return jnp.where(mask != 0, a, b)


@register("vbsl", "generic", cost=scalar_cost(3))
def _vbsl_g(mask, a, b):
    f = jax.vmap(lambda m, x, y: jnp.where(m != 0, x, y))
    return f(jnp.ravel(mask), jnp.ravel(a), jnp.ravel(b)).reshape(a.shape)


def vbsl(mask, a, b):
    return dispatch("vbsl", mask, a, b)


@register("vmla", "vector", cost=vector_cost(2))
def _vmla_v(acc, a, b):
    return acc + a * b


@register("vmla", "generic", cost=scalar_cost(2))
def _vmla_g(acc, a, b):
    f = jax.vmap(lambda c, x, y: c + x * y)
    return f(jnp.ravel(acc), jnp.ravel(a), jnp.ravel(b)).reshape(acc.shape)


def vmla(acc, a, b):
    return dispatch("vmla", acc, a, b)


@register("vmls", "vector", cost=vector_cost(2))
def _vmls_v(acc, a, b):
    return acc - a * b


@register("vmls", "generic", cost=scalar_cost(2))
def _vmls_g(acc, a, b):
    f = jax.vmap(lambda c, x, y: c - x * y)
    return f(jnp.ravel(acc), jnp.ravel(a), jnp.ravel(b)).reshape(acc.shape)


def vmls(acc, a, b):
    return dispatch("vmls", acc, a, b)


@register("vfma", "vector", cost=vector_cost(1))
def _vfma_v(acc, a, b):
    return jnp.asarray(acc) + jnp.asarray(a) * jnp.asarray(b)


@register("vfma", "generic", cost=scalar_cost(1))
def _vfma_g(acc, a, b):
    acc, a, b = jnp.asarray(acc), jnp.asarray(a), jnp.asarray(b)
    shp = jnp.broadcast_shapes(acc.shape, a.shape, b.shape)
    f = jax.vmap(lambda c, x, y: c + x * y)
    return f(jnp.ravel(jnp.broadcast_to(acc, shp)),
             jnp.ravel(jnp.broadcast_to(a, shp)),
             jnp.ravel(jnp.broadcast_to(b, shp))).reshape(shp)


def vfma(acc, a, b):
    return dispatch("vfma", acc, a, b)


# -- register rearrangement (Listing 5: vget_high -> slidedown) --------------

@register("vget_high", "generic", cost=scalar_cost())
def _vgh_g(a):
    # Shape-generic upper-half slice (scalar-loop semantics).  The old
    # vmap(...).T formulation transposed *all* leading axes, which is
    # wrong for ndim > 2.
    n = a.shape[-1]
    return a[..., n // 2:]


@register("vget_high", "pallas", cost=vector_cost(1),
          doc="slidedown by N/2 (paper Listing 5)")
def _vgh_c(a):
    n = a.shape[-1]
    # __riscv_vslidedown_vx: one register-slide instruction.
    return jax.lax.slice_in_dim(a, n // 2, n, axis=-1)


def vget_high(a):
    return dispatch("vget_high", a)


@register("vget_low", "pallas", cost=vector_cost(1), doc="slide/extract low half")
@register("vget_low", "generic", cost=scalar_cost())
def _vgl(a):
    return jax.lax.slice_in_dim(a, 0, a.shape[-1] // 2, axis=-1)


def vget_low(a):
    return dispatch("vget_low", a)


def _combined_width(a, b, *_, **__):
    # result register is the two operands combined (D+D -> Q): the
    # Table-2 rule must see the *output* width, not the inputs'.
    return min(128, 2 * a.size * jnp.dtype(a.dtype).itemsize * 8)


@register("vcombine", "vector", cost=vector_cost(2), width=_combined_width)
@register("vcombine", "generic", cost=scalar_cost(1))
def _vcomb(a, b):
    return jnp.concatenate([a, b], axis=-1)


def vcombine(a, b):
    return dispatch("vcombine", a, b)


@register("vext", "pallas", cost=vector_cost(2), doc="slideup+slidedown merge")
@register("vext", "generic", cost=scalar_cost(2))
def _vext(a, b, n):
    return jnp.concatenate([a[..., n:], b[..., :n]], axis=-1)


def vext(a, b, n):
    return dispatch("vext", a, b, n)


@register("vrev64", "generic", cost=scalar_cost(1))
@register("vrev64", "vector", cost=vector_cost(1))
def _vrev64(a):
    g = 8 // jnp.dtype(a.dtype).itemsize  # elements per 64-bit group
    shp = a.shape[:-1] + (a.shape[-1] // g, g)
    return jnp.flip(a.reshape(shp), axis=-1).reshape(a.shape)


def vrev64(a):
    return dispatch("vrev64", a)


# -- vrbit: the paper's hard case (Listing 7, binary magic numbers) ----------

@register("vrbit", "generic", cost=scalar_cost(8),
          doc="per-element bit loop (scalarized baseline)")
def _vrbit_g(a):
    def rev1(x):
        x = x.astype(jnp.uint8)
        out = jnp.zeros((), jnp.uint8)
        for i in range(8):
            out = out | (((x >> i) & jnp.uint8(1)) << (7 - i))
        return out

    return jax.vmap(rev1)(jnp.ravel(a)).reshape(a.shape).astype(a.dtype)


@register("vrbit", "pallas", cost=vector_cost(15),
          doc="binary-magic-numbers swap network (paper Listing 7 / Freed 1983)")
def _vrbit_c(a):
    # Swap odd/even bits, pairs, then nibbles — 3 stages x (2 shifts, 2 ands,
    # 1 or) = 15 vector instrs per register, vs 8 scalarized ops per element.
    x = a.astype(jnp.uint8)
    x = ((x >> 1) & jnp.uint8(0x55)) | ((x & jnp.uint8(0x55)) << 1)
    x = ((x >> 2) & jnp.uint8(0x33)) | ((x & jnp.uint8(0x33)) << 2)
    x = ((x >> 4) & jnp.uint8(0x0F)) | ((x & jnp.uint8(0x0F)) << 4)
    return x.astype(a.dtype)


def vrbit(a):
    return dispatch("vrbit", a)


# -- broadcast / horizontal reductions ---------------------------------------

def _vdup_scalar_cost(x, shape, *_, **__):
    return int(np.prod(shape)) if shape else 1


def _vdup_width(x, shape, *_, **__):
    # result register width: the scalar operand hides it from the
    # default widest-array inference (same saturation as
    # registry._logical_width_bits)
    elems = int(np.prod(shape)) if shape else 1
    bits = np.dtype(getattr(x, "dtype", np.float32)).itemsize * 8
    return min(128, elems * bits)


@register("vdup", "generic", cost=_vdup_scalar_cost,
          doc="per-lane scalar fill loop")
@register("vdup", "vector", cost=vector_cost(1), width=_vdup_width)
def _vdup(x, shape):
    return jnp.full(shape, x)


def vdup(x, shape):
    return dispatch("vdup", x, shape)


@register("vpadd", "pallas", cost=vector_cost(2), doc="pairwise add via slide+add")
@register("vpadd", "generic", cost=scalar_cost(1))
def _vpadd(a, b):
    c = jnp.concatenate([a, b], axis=-1)
    return c[..., 0::2] + c[..., 1::2]


def vpadd(a, b):
    return dispatch("vpadd", a, b)


@register("vaddv", "vector", cost=vector_cost(1), doc="vredsum")
def _vaddv_v(a):
    return jnp.sum(a, axis=-1)


@register("vaddv", "generic", cost=scalar_cost(1))
def _vaddv_g(a):
    def body(i, acc):
        return acc + a[..., i]
    return jax.lax.fori_loop(0, a.shape[-1], body,
                             jnp.zeros(a.shape[:-1], a.dtype))


def vaddv(a):
    return dispatch("vaddv", a)


@register("vmaxv", "generic", cost=scalar_cost(1))
@register("vmaxv", "vector", cost=vector_cost(1), doc="vredmax")
def _vmaxv(a):
    return jnp.max(a, axis=-1)


def vmaxv(a):
    return dispatch("vmaxv", a)


@register("vminv", "generic", cost=scalar_cost(1))
@register("vminv", "vector", cost=vector_cost(1), doc="vredmin")
def _vminv(a):
    return jnp.min(a, axis=-1)


def vminv(a):
    return dispatch("vminv", a)


# -- reciprocal estimates (Newton-refined on the customized tier) ------------

@register("vrecpe", "generic", cost=scalar_cost(1))
def _vrecpe_g(a):
    return jax.vmap(lambda x: 1.0 / x)(jnp.ravel(a)).reshape(a.shape)


@register("vrecpe", "vector", cost=vector_cost(1))
def _vrecpe_v(a):
    return 1.0 / a


def vrecpe(a):
    return dispatch("vrecpe", a)


# vrecps(a, b) = 2 - a*b: the Newton-Raphson refinement step paired with
# vrecpe (NEON's reciprocal ladder; XNNPACK vsigmoid uses one round).

@register("vrecps", "generic", cost=scalar_cost(2))
def _vrecps_g(a, b):
    f = jax.vmap(lambda x, y: 2.0 - x * y)
    return f(jnp.ravel(a), jnp.ravel(b)).reshape(a.shape)


@register("vrecps", "vector", cost=vector_cost(2))
def _vrecps_v(a, b):
    return 2.0 - a * b


def vrecps(a, b):
    return dispatch("vrecps", a, b)


@register("vrsqrte", "generic", cost=scalar_cost(2))
def _vrsqrte_g(a):
    return jax.vmap(lambda x: 1.0 / jnp.sqrt(x))(jnp.ravel(a)).reshape(a.shape)


@register("vrsqrte", "vector", cost=vector_cost(1))
def _vrsqrte_v(a):
    return jax.lax.rsqrt(a)


def vrsqrte(a):
    return dispatch("vrsqrte", a)


# vrsqrts(a, b) = (3 - a*b) / 2: the refinement step paired with vrsqrte.

@register("vrsqrts", "generic", cost=scalar_cost(3))
def _vrsqrts_g(a, b):
    f = jax.vmap(lambda x, y: (3.0 - x * y) * 0.5)
    return f(jnp.ravel(a), jnp.ravel(b)).reshape(a.shape)


@register("vrsqrts", "vector", cost=vector_cost(3))
def _vrsqrts_v(a, b):
    return (3.0 - a * b) * 0.5


def vrsqrts(a, b):
    return dispatch("vrsqrts", a, b)


@register("vcvt", "generic", cost=scalar_cost(1))
@register("vcvt", "vector", cost=vector_cost(1))
def _vcvt(a, dtype):
    return a.astype(dtype)


def vcvt(a, dtype):
    return dispatch("vcvt", a, dtype)


@register("vzip", "pallas", cost=vector_cost(2), width=_combined_width,
          doc="interleave via vrgather")
@register("vzip", "generic", cost=scalar_cost(2))
def _vzip(a, b):
    return jnp.stack([a, b], axis=-1).reshape(a.shape[:-1] + (2 * a.shape[-1],))


def vzip(a, b):
    return dispatch("vzip", a, b)



def _strip_width(bits: int) -> int:
    """Saturate a logical-register width at NEON Q-register (strip)
    granularity — the same rule as registry._logical_width_bits.  A
    register group wider than one strip (a re-vectorized widened strip,
    or the wide side of a vwmul) strip-mines across groups rather than
    invalidating the tier; the cost models charge the extra register
    micro-ops."""
    return min(128, bits)


# -- memory ops (the port frontend's load/store surface) ---------------------
#
# ``vld1``/``vst1`` mirror NEON's unit-stride load/store intrinsics in
# functional form: a "pointer" is a (buffer, element offset) pair, and a
# store returns the updated buffer.  The logical register is exactly
# ``lanes`` elements, so the Table-2 width rule must see that — not the
# backing buffer's size (which _logical_width_bits would saturate at
# Q-register width) — hence the explicit ``width=``/``cost=`` models.

def _vld1_width(buf, offset, lanes, *_, **__):
    return _strip_width(int(lanes) * jnp.dtype(buf.dtype).itemsize * 8)


def _vld1_cost(buf, offset, lanes, *_, **__):
    from .trace import vinstrs_for
    return vinstrs_for(int(lanes), buf.dtype)


def _vld1_scalar_cost(buf, offset, lanes, *_, **__):
    return int(lanes)


@register("vld1", "vector", cost=_vld1_cost, width=_vld1_width,
          doc="unit-stride whole-register load (vle<eew>.v)")
def _vld1_v(buf, offset, lanes):
    if lanes > buf.shape[0]:
        # register wider than the whole buffer: only reachable from a
        # never-executed (zero-trip) loop body, but tracing still needs
        # a shape-valid load — clamped gather keeps it in bounds
        idx = jnp.clip(offset + jnp.arange(lanes), 0, buf.shape[0] - 1)
        return buf[idx]
    return jax.lax.dynamic_slice_in_dim(buf, offset, lanes, axis=0)


@register("vld1", "generic", cost=_vld1_scalar_cost,
          doc="per-lane scalar load loop")
def _vld1_g(buf, offset, lanes):
    return jax.vmap(lambda i: jax.lax.dynamic_index_in_dim(
        buf, i, axis=0, keepdims=False))(offset + jnp.arange(lanes))


def vld1(buf, offset, lanes):
    """Load ``lanes`` contiguous elements of ``buf`` starting at
    ``offset`` into a logical register."""
    return dispatch("vld1", buf, offset, lanes)


def _vst1_width(buf, offset, val, *_, **__):
    return _strip_width(int(np.prod(val.shape) or 1) *
                        jnp.dtype(val.dtype).itemsize * 8)


def _vst1_cost(buf, offset, val, *_, **__):
    from .trace import vinstrs_for
    return vinstrs_for(int(np.prod(val.shape) or 1), val.dtype)


def _vst1_scalar_cost(buf, offset, val, *_, **__):
    return int(np.prod(val.shape) or 1)


@register("vst1", "vector", cost=_vst1_cost, width=_vst1_width,
          doc="unit-stride whole-register store (vse<eew>.v)")
def _vst1_v(buf, offset, val):
    if val.shape[0] > buf.shape[0]:
        # see _vld1_v: trace-safety for zero-trip widened strip bodies
        return buf.at[offset + jnp.arange(val.shape[0])].set(
            val, mode="drop")
    return jax.lax.dynamic_update_slice_in_dim(buf, val, offset, axis=0)


@register("vst1", "generic", cost=_vst1_scalar_cost,
          doc="per-lane scalar store loop")
def _vst1_g(buf, offset, val):
    def body(i, acc):
        return acc.at[offset + i].set(val[i])
    return jax.lax.fori_loop(0, val.shape[0], body, buf)


def vst1(buf, offset, val):
    """Store register ``val`` into ``buf`` at element ``offset``;
    returns the updated buffer (functional-store semantics)."""
    return dispatch("vst1", buf, offset, val)


# -- masked (predicated) memory ops ------------------------------------------
#
# The RVV tail story: instead of a scalar cleanup loop, one more strip
# iteration runs with the active length set below the register width
# (``vsetvli`` semantics).  ``vld1m``/``vst1m`` are the logical-ISA form:
# the first ``cnt`` lanes are live; masked-off load lanes read as zero
# and masked-off store lanes leave memory untouched.  One predicated
# whole-register instruction either way, which is what the cost models
# charge — predication is architecturally free on RVV.

def _vld1m_width(buf, offset, lanes, cnt, fill=0, *_, **__):
    return _strip_width(int(lanes) * jnp.dtype(buf.dtype).itemsize * 8)


def _vld1m_cost(buf, offset, lanes, cnt, fill=0, *_, **__):
    from .trace import vinstrs_for
    return vinstrs_for(int(lanes), buf.dtype)


@register("vld1m", "vector", cost=_vld1m_cost, width=_vld1m_width,
          doc="predicated unit-stride load (vsetvli cnt; vle<eew>.v)")
def _vld1m_v(buf, offset, lanes, cnt, fill=0):
    lane = jnp.arange(lanes)
    idx = jnp.clip(offset + lane, 0, buf.shape[0] - 1)
    return jnp.where(lane < cnt, buf[idx], jnp.asarray(fill, buf.dtype))


@register("vld1m", "generic", cost=lambda buf, offset, lanes, cnt,
          fill=0, *_, **__: int(lanes),
          doc="per-lane guarded scalar load loop")
def _vld1m_g(buf, offset, lanes, cnt, fill=0):
    def one(i):
        safe = jnp.clip(offset + i, 0, buf.shape[0] - 1)
        v = jax.lax.dynamic_index_in_dim(buf, safe, axis=0, keepdims=False)
        return jnp.where(i < cnt, v, jnp.asarray(fill, buf.dtype))
    return jax.vmap(one)(jnp.arange(lanes))


def vld1m(buf, offset, lanes, cnt, fill=0):
    """Load ``lanes`` elements at ``offset`` with only the first ``cnt``
    active; inactive lanes read as ``fill`` (never out of bounds)."""
    return dispatch("vld1m", buf, offset, lanes, cnt, fill)


def _vst1m_width(buf, offset, val, cnt, *_, **__):
    return _strip_width(int(np.prod(val.shape) or 1) *
                        jnp.dtype(val.dtype).itemsize * 8)


def _vst1m_cost(buf, offset, val, cnt, *_, **__):
    from .trace import vinstrs_for
    return vinstrs_for(int(np.prod(val.shape) or 1), val.dtype)


@register("vst1m", "vector", cost=_vst1m_cost, width=_vst1m_width,
          doc="predicated unit-stride store (vsetvli cnt; vse<eew>.v)")
@register("vst1m", "generic", cost=lambda buf, offset, val, cnt,
          *_, **__: int(np.prod(val.shape) or 1),
          doc="per-lane guarded scalar store loop")
def _vst1m(buf, offset, val, cnt):
    lane = jnp.arange(val.shape[0])
    # masked-off lanes target index == len(buf): dropped by scatter mode
    idx = jnp.where(lane < cnt, offset + lane, buf.shape[0])
    return buf.at[idx].set(val, mode="drop")


def vst1m(buf, offset, val, cnt):
    """Store the first ``cnt`` lanes of ``val`` into ``buf`` at
    ``offset``; returns the updated buffer."""
    return dispatch("vst1m", buf, offset, val, cnt)


# -- vtile: loop-invariant register widening ---------------------------------
#
# When the re-vectorizer widens a strip by ``reps``, loop-invariant
# registers set up before the loop (vdup'd constants, per-channel
# vld1'd scale/bias) must repeat their lane pattern across the widened
# register.  On RVV this is a register-group move/slide sequence.

def _vtile_width(a, reps, *_, **__):
    return _strip_width(int(np.prod(a.shape) or 1) * int(reps) *
                        jnp.dtype(a.dtype).itemsize * 8)


def _vtile_cost(a, reps, *_, **__):
    from .trace import vinstrs_for
    return vinstrs_for(int(np.prod(a.shape) or 1) * int(reps), a.dtype)


@register("vtile", "vector", cost=_vtile_cost, width=_vtile_width,
          doc="repeat lane pattern across a widened register group")
@register("vtile", "generic", cost=lambda a, reps, *_, **__:
          int(np.prod(a.shape) or 1) * int(reps))
def _vtile(a, reps):
    return jnp.tile(a, int(reps))


def vtile(a, reps):
    """Repeat register ``a``'s lanes ``reps`` times (widened register)."""
    return dispatch("vtile", a, reps)


# -- vld1g: group-broadcast load (a walking vld1_dup, re-tiled) --------------
#
# When the re-vectorizer widens a strip whose body broadcasts one fresh
# scalar per iteration (qs8gemm's ``vld1_dup_s8(a); a += 1``), the
# widened body needs ``groups`` consecutive scalars each repeated across
# ``reps`` lanes: ``result[lane] = buf[offset + lane // reps]``.  On RVV
# this is a narrow vle of the scalars plus one vrgather through a
# ``lane >> log2(reps)`` index register.

def _vld1g_width(buf, offset, reps, groups, *_, **__):
    return _strip_width(int(reps) * int(groups) *
                        jnp.dtype(buf.dtype).itemsize * 8)


def _vld1g_cost(buf, offset, reps, groups, *_, **__):
    from .trace import vinstrs_for
    return vinstrs_for(int(reps) * int(groups), buf.dtype)


@register("vld1g", "vector", cost=_vld1g_cost, width=_vld1g_width,
          doc="group-broadcast load (vle + vid/vsrl/vrgather)")
@register("vld1g", "generic", cost=lambda buf, offset, reps, groups,
          *_, **__: int(groups) + int(reps) * int(groups),
          doc="scalar loads + per-lane broadcast loop")
def _vld1g(buf, offset, reps, groups):
    lane = jnp.arange(int(reps) * int(groups))
    # clamped gather: trace-safe for zero-trip widened bodies (see vld1)
    idx = jnp.clip(offset + lane // int(reps), 0, buf.shape[0] - 1)
    return buf[idx]


def vld1g(buf, offset, reps, groups):
    """Load ``groups`` consecutive scalars at ``offset`` and broadcast
    each across ``reps`` lanes (``out[lane] = buf[offset+lane//reps]``)."""
    return dispatch("vld1g", buf, offset, reps, groups)


def _vld1gm_width(buf, offset, reps, groups, cnt, fill=0, *_, **__):
    return _strip_width(int(reps) * int(groups) *
                        jnp.dtype(buf.dtype).itemsize * 8)


def _vld1gm_cost(buf, offset, reps, groups, cnt, fill=0, *_, **__):
    from .trace import vinstrs_for
    return vinstrs_for(int(reps) * int(groups), buf.dtype)


@register("vld1gm", "vector", cost=_vld1gm_cost, width=_vld1gm_width,
          doc="predicated group-broadcast load (vsetvli cnt groups)")
@register("vld1gm", "generic", cost=lambda buf, offset, reps, groups,
          cnt, fill=0, *_, **__: int(reps) * int(groups),
          doc="per-lane guarded broadcast loop")
def _vld1gm(buf, offset, reps, groups, cnt, fill=0):
    lane = jnp.arange(int(reps) * int(groups))
    g = lane // int(reps)
    idx = jnp.clip(offset + g, 0, buf.shape[0] - 1)
    return jnp.where(g < cnt, buf[idx], jnp.asarray(fill, buf.dtype))


def vld1gm(buf, offset, reps, groups, cnt, fill=0):
    """Masked :func:`vld1g`: only the first ``cnt`` scalar groups are
    active; lanes of inactive groups read as ``fill``."""
    return dispatch("vld1gm", buf, offset, reps, groups, cnt, fill)


# -- vfold: additive accumulator group fold (widened -> narrow) --------------
#
# A widened additive accumulator carries ``factor`` interleaved narrow
# accumulators: narrow lane l of the fold is the sum over groups g of
# wide lane ``g*lanes + l``.  Integer adds are modular so the fold is
# bitwise exact; float folds reassociate exactly like the halving
# vslidedown+vfadd ladder the RVV emitter retires.

def _vfold_width(a, factor, *_, **__):
    return _strip_width(int(np.prod(a.shape) or 1) *
                        jnp.dtype(a.dtype).itemsize * 8)


def _vfold_cost(a, factor, *_, **__):
    from .trace import vinstrs_for
    steps = max(1, int(factor).bit_length() - 1)
    lanes = int(np.prod(a.shape) or 1)
    # halving ladder: one slidedown + one add per step at shrinking vl
    return 2 * steps * max(1, vinstrs_for(max(1, lanes // 2), a.dtype))


@register("vfold", "vector", cost=_vfold_cost, width=_vfold_width,
          doc="halving vslidedown+add ladder over the register group")
@register("vfold", "generic", cost=lambda a, factor, *_, **__:
          int(np.prod(a.shape) or 1))
def _vfold(a, factor):
    f = int(factor)
    lanes = a.shape[0] // f
    return jnp.sum(a.reshape(f, lanes), axis=0, dtype=a.dtype)


def vfold(a, factor):
    """Fold a ``factor``-times widened additive accumulator back to its
    narrow width by summing the ``factor`` interleaved groups."""
    return dispatch("vfold", a, factor)


# -- saturating arithmetic (vqadd/vqsub) -------------------------------------

def _sat_math(x, y, sub: bool):
    """Branchless saturating add/sub — no widening, so it is exact for
    every integer lane width without x64 mode."""
    dt = x.dtype
    if not jnp.issubdtype(dt, jnp.integer):
        return (x - y if sub else x + y).astype(dt)
    info = jnp.iinfo(dt)
    s = (x - y) if sub else (x + y)           # wraps on overflow
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        if sub:
            return jnp.where(y > x, jnp.zeros((), dt), s)
        return jnp.where(s < x, jnp.full((), info.max, dt), s)
    # signed: overflow iff operand signs admit it and result sign flipped
    ovf = ((x ^ y) & (x ^ s) if sub else (x ^ s) & (y ^ s)) < 0
    sat = jnp.where(x < 0, jnp.full((), info.min, dt),
                    jnp.full((), info.max, dt))
    return jnp.where(ovf, sat, s)


def _saturate(op_name, sub):
    @register(op_name, "generic", cost=scalar_cost(3),
              doc="per-element overflow-check loop")
    def _g(a, b):
        f = jax.vmap(lambda x, y: _sat_math(x, y, sub))
        return f(jnp.ravel(a),
                 jnp.ravel(jnp.broadcast_to(b, jnp.shape(a)))
                 ).reshape(jnp.shape(a))

    # RVV has native saturating adds (vsadd/vssub): one instruction.
    @register(op_name, "vector", cost=vector_cost(1),
              doc="native saturating op (vsadd/vssub)")
    def _v(a, b):
        return _sat_math(a, b, sub)

    def api(a, b):
        return dispatch(op_name, a, b)

    api.__name__ = op_name
    return api


vqadd = _saturate("vqadd", sub=False)
vqsub = _saturate("vqsub", sub=True)


# -- vreinterpret: register bit reinterpretation -----------------------------
#
# A pure type-level cast on the register file (free on RVV — the vector
# register has no element type); the logical form reshapes lanes so the
# total bit pattern is preserved (little-endian, matching NEON).

@register("vreinterpret", "vector", cost=lambda *a, **k: 0,
          doc="register reinterpret (free: no data movement)")
@register("vreinterpret", "generic", cost=scalar_cost(1))
def _vreinterpret(a, dtype):
    src, dst = jnp.dtype(a.dtype), jnp.dtype(dtype)
    if src == dst:
        return a
    if src.itemsize == dst.itemsize:
        return jax.lax.bitcast_convert_type(a, dst)
    total = a.shape[-1] * src.itemsize
    out_lanes = total // dst.itemsize
    if src.itemsize < dst.itemsize:
        g = dst.itemsize // src.itemsize
        x = a.reshape(a.shape[:-1] + (out_lanes, g))
        return jax.lax.bitcast_convert_type(x, dst)
    x = jax.lax.bitcast_convert_type(a, dst)    # adds a trailing group dim
    return x.reshape(a.shape[:-1] + (out_lanes,))


def vreinterpret(a, dtype):
    return dispatch("vreinterpret", a, dtype)


# -- widening arithmetic (vmull/vaddl/vsubl -> RVV vwmul/vwadd/vwsub) --------
#
# NEON's width-changing families are where the paper's customized
# conversions matter most (Table 2): the generic-union route converts
# both operands up and operates at the wide width (3 wide ops), while
# RVV has single widening instructions that read narrow groups and
# write one double-width group.  Ops take the *output* dtype explicitly
# (like vcvt) — the logical register model has no implicit promotion.

def _wide_out_width(a, b, dtype, *_, **__):
    # result register: same element count at 2x width
    n = int(np.prod(a.shape) or 1)
    return _strip_width(n * jnp.dtype(dtype).itemsize * 8)


def _wide_out_cost(ops_per_vec):
    def cost(a, b, dtype, *_, **__):
        from .trace import vinstrs_for
        return ops_per_vec * vinstrs_for(int(np.prod(a.shape) or 1),
                                         dtype)
    return cost


def _widening(op_name, jnp_fn, doc):
    @register(op_name, "generic",
              cost=lambda a, b, dtype, *_, **__:
              int(np.prod(a.shape) or 1),
              doc="per-element widen-and-op loop")
    def _g(a, b, dtype):
        f = jax.vmap(lambda x, y: jnp_fn(x.astype(dtype),
                                         y.astype(dtype)))
        return f(jnp.ravel(a), jnp.ravel(b)).reshape(a.shape)

    # the non-customized conversion: two widening converts + a wide op
    @register(op_name, "vector", cost=_wide_out_cost(3),
              width=_wide_out_width, doc="cvt + cvt + wide op")
    def _v(a, b, dtype):
        return jnp_fn(a.astype(dtype), b.astype(dtype))

    # customized conversion: one widening instruction (vwmul/vwadd/
    # vwsub) retiring only the double-width destination group's micro-ops
    @register(op_name, "pallas", cost=_wide_out_cost(1),
              width=_wide_out_width, doc=doc)
    def _c(a, b, dtype):
        return jnp_fn(a.astype(dtype), b.astype(dtype))

    def api(a, b, dtype):
        return dispatch(op_name, a, b, dtype)

    api.__name__ = op_name
    return api


vmull = _widening("vmull", jnp.multiply,
                  "single widening multiply (vwmul.vv)")
vaddl = _widening("vaddl", jnp.add, "single widening add (vwadd.vv)")
vsubl = _widening("vsubl", jnp.subtract, "single widening sub (vwsub.vv)")


# -- widening multiply-accumulate (vmlal/vmlsl -> RVV vwmacc) ----------------
#
# NEON's vmlal_<t> reads two narrow D registers and accumulates their
# double-width products into a Q accumulator — the inner op of every
# int8 dot/gemm microkernel.  RVV's vwmacc.vv does it in one
# instruction (vd[2*SEW] += vs1[SEW] * vs2[SEW]); the non-customized
# route is two widening converts plus a wide fma.  vmlsl negates the
# product (vwmacc on a negated operand / vwmacsu pattern).

def _wide_macc_width(acc, a, b, dtype, *_, **__):
    # destination register group: the accumulator at the wide width
    n = int(np.prod(np.shape(acc)) or 1)
    return _strip_width(n * jnp.dtype(dtype).itemsize * 8)


def _wide_macc_cost(ops_per_vec):
    def cost(acc, a, b, dtype, *_, **__):
        from .trace import vinstrs_for
        return ops_per_vec * vinstrs_for(int(np.prod(np.shape(a)) or 1),
                                         dtype)
    return cost


def _widening_macc(op_name, sign, doc):
    @register(op_name, "generic",
              cost=lambda acc, a, b, dtype, *_, **__:
              int(np.prod(np.shape(a)) or 1),
              doc="per-element widen-mul-accumulate loop")
    def _g(acc, a, b, dtype):
        f = jax.vmap(lambda c, x, y:
                     c + sign * (x.astype(dtype) * y.astype(dtype)))
        return f(jnp.ravel(acc), jnp.ravel(a),
                 jnp.ravel(b)).reshape(jnp.shape(acc))

    # non-customized conversion: widen both operands, then a wide fma
    @register(op_name, "vector", cost=_wide_macc_cost(3),
              width=_wide_macc_width, doc="cvt + cvt + wide fma")
    def _v(acc, a, b, dtype):
        return acc + sign * (a.astype(dtype) * b.astype(dtype))

    # customized conversion: a single widening multiply-accumulate
    # retiring only the double-width destination group's micro-ops
    @register(op_name, "pallas", cost=_wide_macc_cost(1),
              width=_wide_macc_width, doc=doc)
    def _c(acc, a, b, dtype):
        return acc + sign * (a.astype(dtype) * b.astype(dtype))

    def api(acc, a, b, dtype):
        return dispatch(op_name, acc, a, b, dtype)

    api.__name__ = op_name
    return api


vmlal = _widening_macc("vmlal", 1,
                       "single widening multiply-accumulate (vwmacc.vv)")
vmlsl = _widening_macc("vmlsl", -1,
                       "single widening multiply-subtract "
                       "(vwmacc.vv on the negated multiplicand)")


def _cvt_out_width(a, dtype, *_, **__):
    # width rule sees the wider of source and destination registers
    n = int(np.prod(a.shape) or 1)
    bits = n * max(jnp.dtype(a.dtype).itemsize,
                   jnp.dtype(dtype).itemsize) * 8
    return _strip_width(bits)


def _cvt_out_cost(ops_per_vec):
    def cost(a, dtype, *_, **__):
        from .trace import vinstrs_for
        n = int(np.prod(a.shape) or 1)
        wide = a.dtype if jnp.dtype(a.dtype).itemsize >= \
            jnp.dtype(dtype).itemsize else jnp.dtype(dtype)
        return ops_per_vec * vinstrs_for(n, wide)
    return cost


@register("vmovl", "vector", cost=_cvt_out_cost(1), width=_cvt_out_width,
          doc="widening move (vsext/vzext.vf2)")
@register("vmovl", "generic", cost=scalar_cost(1))
def _vmovl(a, dtype):
    return a.astype(dtype)


def vmovl(a, dtype):
    return dispatch("vmovl", a, dtype)


def _wrap_narrow(a, dtype):
    """Truncating narrow (vmovn semantics: keep the low half bits)."""
    dst = jnp.dtype(dtype)
    src_u = jnp.dtype(f"uint{jnp.dtype(a.dtype).itemsize * 8}")
    dst_u = jnp.dtype(f"uint{dst.itemsize * 8}")
    x = a if a.dtype == src_u else jax.lax.bitcast_convert_type(a, src_u)
    x = (x & src_u.type(2 ** (dst_u.itemsize * 8) - 1)).astype(dst_u)
    return x if dst == dst_u else jax.lax.bitcast_convert_type(x, dst)


@register("vmovn", "pallas", cost=_cvt_out_cost(1), width=_cvt_out_width,
          doc="single narrowing move (vncvt)")
@register("vmovn", "vector", cost=_cvt_out_cost(2), width=_cvt_out_width,
          doc="mask + convert at the wide width")
def _vmovn_v(a, dtype):
    return _wrap_narrow(a, dtype)


@register("vmovn", "generic", cost=scalar_cost(1))
def _vmovn_g(a, dtype):
    return jax.vmap(lambda x: _wrap_narrow(x, dtype))(
        jnp.ravel(a)).reshape(a.shape)


def vmovn(a, dtype):
    return dispatch("vmovn", a, dtype)


def _sat_narrow(a, dtype):
    dst = jnp.dtype(dtype)
    info = jnp.iinfo(dst)
    # clamp sub-32-bit lanes in 32 bits: XLA:TPU clamps an int16
    # arithmetic right shift's negative lanes to the upper bound
    if a.dtype.itemsize < 4:
        a = a.astype(jnp.int32)
    return jnp.clip(a, info.min, info.max).astype(dst)


def _sat_narrowing(op_name, doc):
    @register(op_name, "generic", cost=scalar_cost(3),
              doc="per-element clamp-and-narrow loop")
    def _g(a, dtype):
        return jax.vmap(lambda x: _sat_narrow(x, dtype))(
            jnp.ravel(a)).reshape(a.shape)

    @register(op_name, "vector", cost=_cvt_out_cost(3),
              width=_cvt_out_width, doc="min + max + convert (wide)")
    def _v(a, dtype):
        return _sat_narrow(a, dtype)

    # RVV narrows with saturation in one instruction
    @register(op_name, "pallas", cost=_cvt_out_cost(1),
              width=_cvt_out_width, doc=doc)
    def _c(a, dtype):
        return _sat_narrow(a, dtype)

    def api(a, dtype):
        return dispatch(op_name, a, dtype)

    api.__name__ = op_name
    return api


vqmovn = _sat_narrowing("vqmovn", "single saturating narrow (vnclip)")
vqmovun = _sat_narrowing("vqmovun",
                         "single saturating narrow to unsigned (vnclipu)")


# -- struct loads/stores (vld2/vld3/vld4 -> RVV segment loads) ---------------
#
# ``vld<n>`` reads n*lanes contiguous elements and de-interleaves them
# into an n-register tuple (lane j of member i is element n*j+i);
# ``vst<n>`` is the inverse.  RVV's segment instructions
# (vlseg<n>e/vsseg<n>e) do the whole group in one instruction; without
# them the vector tier needs n strided accesses per struct.  Pointers
# follow the vld1 convention: (buffer, element offset), stores return
# the updated buffer.

def _interleave(*vs):
    return jnp.stack(vs, axis=-1).reshape(len(vs) * vs[0].shape[0])


def _register_segment_family(n):
    """Register vld<n>/vst<n> and the masked vld<n>m/vst<n>m forms.

    All arities share one shape: the Table-2 width is *per member
    register* (vld2q_f32 is native on rvv-128); the segment tier costs
    one grouped access over n*lanes elements, the strided fallback n
    accesses plus n pointer adjusts."""

    def ld_width(buf, offset, lanes, *_, **__):
        return _strip_width(int(lanes) * jnp.dtype(buf.dtype).itemsize * 8)

    def ld_seg_cost(buf, offset, lanes, *_, **__):
        from .trace import vinstrs_for
        return vinstrs_for(n * int(lanes), buf.dtype)

    def ld_strided_cost(buf, offset, lanes, *_, **__):
        from .trace import vinstrs_for
        return n * vinstrs_for(int(lanes), buf.dtype) + n

    def ld_v(buf, offset, lanes):
        total = n * lanes
        if total > buf.shape[0]:
            # zero-trip trace safety, as in _vld1_v
            idx = jnp.clip(offset + jnp.arange(total), 0, buf.shape[0] - 1)
            x = buf[idx]
        else:
            x = jax.lax.dynamic_slice_in_dim(buf, offset, total, axis=0)
        return tuple(x[i::n] for i in range(n))

    def ld_g(buf, offset, lanes):
        def at(i):
            return jax.lax.dynamic_index_in_dim(buf, i, axis=0,
                                                keepdims=False)
        lane = jnp.arange(lanes)
        return tuple(jax.vmap(at)(offset + n * lane + i)
                     for i in range(n))

    register(f"vld{n}", "pallas", cost=ld_seg_cost, width=ld_width,
             doc=f"one segment load (vlseg{n}e<eew>.v)")(ld_v)
    register(f"vld{n}", "vector", cost=ld_strided_cost, width=ld_width,
             doc=f"{n} strided loads (vlse<eew>.v)")(ld_v)
    register(f"vld{n}", "generic",
             cost=lambda buf, offset, lanes, *_, **__: n * int(lanes),
             doc="per-lane scalar gather loop")(ld_g)

    def st_width(buf, offset, *vs, **__):
        v0 = vs[0]
        return _strip_width(int(np.prod(v0.shape) or 1) *
                            jnp.dtype(v0.dtype).itemsize * 8)

    def st_seg_cost(buf, offset, *vs, **__):
        from .trace import vinstrs_for
        return vinstrs_for(n * int(np.prod(vs[0].shape) or 1),
                           vs[0].dtype)

    def st_strided_cost(buf, offset, *vs, **__):
        from .trace import vinstrs_for
        return n * vinstrs_for(int(np.prod(vs[0].shape) or 1),
                               vs[0].dtype) + n

    def st_v(buf, offset, *vs):
        val = _interleave(*vs[:n])
        if val.shape[0] > buf.shape[0]:
            return buf.at[offset + jnp.arange(val.shape[0])].set(
                val, mode="drop")
        return jax.lax.dynamic_update_slice_in_dim(buf, val, offset,
                                                   axis=0)

    register(f"vst{n}", "pallas", cost=st_seg_cost, width=st_width,
             doc=f"one segment store (vsseg{n}e<eew>.v)")(st_v)
    register(f"vst{n}", "vector", cost=st_strided_cost, width=st_width,
             doc=f"{n} strided stores (vsse<eew>.v)")(st_v)
    register(f"vst{n}", "generic",
             cost=lambda buf, offset, *vs, **__:
             n * int(np.prod(vs[0].shape) or 1),
             doc="per-lane scalar scatter loop")(st_v)

    # masked (predicated) forms — the re-vectorizer's lane-group tail:
    # the first ``cnt`` element *groups* are live, exactly vsetvli
    # semantics applied to a segment access.

    def ldm_v(buf, offset, lanes, cnt, fill=0):
        lane = jnp.arange(lanes)
        f = jnp.asarray(fill, buf.dtype)
        return tuple(
            jnp.where(lane < cnt,
                      buf[jnp.clip(offset + n * lane + i, 0,
                                   buf.shape[0] - 1)], f)
            for i in range(n))

    register(f"vld{n}m", "vector", cost=ld_seg_cost, width=ld_width,
             doc=f"predicated segment load (vsetvli cnt; "
                 f"vlseg{n}e<eew>.v)")(ldm_v)
    register(f"vld{n}m", "generic",
             cost=lambda buf, offset, lanes, cnt, fill=0, *_, **__:
             n * int(lanes),
             doc="per-lane guarded scalar gather loop")(ldm_v)

    def stm(buf, offset, *args):
        vs, cnt = args[:n], args[n]
        val = _interleave(*vs)
        pos = jnp.arange(val.shape[0])
        idx = jnp.where(pos // n < cnt, offset + pos, buf.shape[0])
        return buf.at[idx].set(val, mode="drop")

    register(f"vst{n}m", "vector", cost=st_seg_cost, width=st_width,
             doc=f"predicated segment store (vsetvli cnt; "
                 f"vsseg{n}e<eew>.v)")(stm)
    register(f"vst{n}m", "generic",
             cost=lambda buf, offset, *vs, **__:
             n * int(np.prod(vs[0].shape) or 1),
             doc="per-lane guarded scalar scatter loop")(stm)


for _n in (2, 3, 4):
    _register_segment_family(_n)
del _n


def vld2(buf, offset, lanes):
    """De-interleaving struct load: ``(buf[off::2], buf[off+1::2])``
    limited to ``lanes`` elements each."""
    return dispatch("vld2", buf, offset, lanes)


def vst2(buf, offset, v0, v1):
    """Interleaving struct store; returns the updated buffer."""
    return dispatch("vst2", buf, offset, v0, v1)


def vld2m(buf, offset, lanes, cnt, fill=0):
    """Masked :func:`vld2`: only the first ``cnt`` element pairs are
    active; inactive lanes read as ``fill`` (never out of bounds)."""
    return dispatch("vld2m", buf, offset, lanes, cnt, fill)


def vst2m(buf, offset, v0, v1, cnt):
    """Masked :func:`vst2`: stores the first ``cnt`` element pairs."""
    return dispatch("vst2m", buf, offset, v0, v1, cnt)


def vld3(buf, offset, lanes):
    """3-way de-interleaving struct load (vlseg3e): lane j of member i
    is element ``offset + 3*j + i``."""
    return dispatch("vld3", buf, offset, lanes)


def vst3(buf, offset, v0, v1, v2):
    """3-way interleaving struct store; returns the updated buffer."""
    return dispatch("vst3", buf, offset, v0, v1, v2)


def vld3m(buf, offset, lanes, cnt, fill=0):
    """Masked :func:`vld3`: first ``cnt`` element triples active."""
    return dispatch("vld3m", buf, offset, lanes, cnt, fill)


def vst3m(buf, offset, v0, v1, v2, cnt):
    """Masked :func:`vst3`: stores the first ``cnt`` element triples."""
    return dispatch("vst3m", buf, offset, v0, v1, v2, cnt)


def vld4(buf, offset, lanes):
    """4-way de-interleaving struct load (vlseg4e)."""
    return dispatch("vld4", buf, offset, lanes)


def vst4(buf, offset, v0, v1, v2, v3):
    """4-way interleaving struct store; returns the updated buffer."""
    return dispatch("vst4", buf, offset, v0, v1, v2, v3)


def vld4m(buf, offset, lanes, cnt, fill=0):
    """Masked :func:`vld4`: first ``cnt`` element quads active."""
    return dispatch("vld4m", buf, offset, lanes, cnt, fill)


def vst4m(buf, offset, v0, v1, v2, v3, cnt):
    """Masked :func:`vst4`: stores the first ``cnt`` element quads."""
    return dispatch("vst4m", buf, offset, v0, v1, v2, v3, cnt)


@register("vtbl", "generic", cost=scalar_cost(2), doc="per-lane table lookup")
def _vtbl_g(table, idx):
    return jax.vmap(lambda i: table[..., i])(jnp.ravel(idx)).reshape(idx.shape)


@register("vtbl", "vector", cost=vector_cost(2), doc="vrgather")
def _vtbl_v(table, idx):
    return jnp.take(table, idx, axis=-1)


def vtbl(table, idx):
    return dispatch("vtbl", table, idx)


# ---------------------------------------------------------------------------
# RVV codegen metadata (consumed by repro.rvv.codegen)
# ---------------------------------------------------------------------------
#
# Per logical-ISA op: the real RVV mnemonic expansion the code generator
# emits, keyed by the operand's dtype class ("int" / "uint" / "float").
# Each entry is the *retired-instruction* sequence for one issue of the
# op (vsetvli toggles around predicated sites are accounted separately
# by the emitter).  ``shape`` documents the operand form.  This table is
# the single source of truth: repro.rvv.codegen refuses to emit a
# mnemonic that is not listed here, and DESIGN.md §12's supported-
# instruction table is generated from it.
#
# Width-changing families operate at the *narrow* SEW with a 2x-EMUL
# wide operand (the RVV widening/narrowing convention); segment loads
# and stores retire a single vlseg<n>e/vsseg<n>e instruction.

RVV_MNEMONICS = {
    # simple arithmetic / logic (Listing 8: the vector tier maps 1:1)
    "vadd":  {"shape": "vv", "int": ("vadd.vv",), "uint": ("vadd.vv",),
              "float": ("vfadd.vv",)},
    "vsub":  {"shape": "vv", "int": ("vsub.vv",), "uint": ("vsub.vv",),
              "float": ("vfsub.vv",)},
    "vmul":  {"shape": "vv", "int": ("vmul.vv",), "uint": ("vmul.vv",),
              "float": ("vfmul.vv",)},
    "vmax":  {"shape": "vv", "int": ("vmax.vv",), "uint": ("vmaxu.vv",),
              "float": ("vfmax.vv",)},
    "vmin":  {"shape": "vv", "int": ("vmin.vv",), "uint": ("vminu.vv",),
              "float": ("vfmin.vv",)},
    "vand":  {"shape": "vv", "int": ("vand.vv",), "uint": ("vand.vv",)},
    "vorr":  {"shape": "vv", "int": ("vor.vv",), "uint": ("vor.vv",)},
    "veor":  {"shape": "vv", "int": ("vxor.vv",), "uint": ("vxor.vv",)},
    # saturating add/sub: the fixed-point ops (vxrm does not matter at
    # shift 0, but vsadd/vssub saturate exactly like vqadd/vqsub)
    "vqadd": {"shape": "vv", "int": ("vsadd.vv",), "uint": ("vsaddu.vv",)},
    "vqsub": {"shape": "vv", "int": ("vssub.vv",), "uint": ("vssubu.vv",)},
    # multiply-accumulate (vd overlays the accumulator operand)
    "vmla":  {"shape": "vvv", "int": ("vmacc.vv",), "uint": ("vmacc.vv",),
              "float": ("vfmacc.vv",)},
    "vmls":  {"shape": "vvv", "int": ("vnmsac.vv",),
              "uint": ("vnmsac.vv",), "float": ("vfnmsac.vv",)},
    "vfma":  {"shape": "vvv", "float": ("vfmacc.vv",)},
    # immediate shifts
    "vshl_n": {"shape": "vx", "int": ("vsll.vx",), "uint": ("vsll.vx",)},
    "vshr_n": {"shape": "vx", "int": ("vsra.vx",), "uint": ("vsrl.vx",)},
    # compares: paper Listing 6 — build zeros, compare to a mask
    # register, merge all-ones under the mask
    "vceq": {"shape": "vv->umask", "int": ("vmv.v.x", "vmseq.vv",
             "vmerge.vxm"), "uint": ("vmv.v.x", "vmseq.vv",
             "vmerge.vxm"), "float": ("vmv.v.x", "vmfeq.vv",
             "vmerge.vxm")},
    "vcgt": {"shape": "vv->umask", "int": ("vmv.v.x", "vmslt.vv",
             "vmerge.vxm"), "uint": ("vmv.v.x", "vmsltu.vv",
             "vmerge.vxm"), "float": ("vmv.v.x", "vmflt.vv",
             "vmerge.vxm")},
    "vcge": {"shape": "vv->umask", "int": ("vmv.v.x", "vmsle.vv",
             "vmerge.vxm"), "uint": ("vmv.v.x", "vmsleu.vv",
             "vmerge.vxm"), "float": ("vmv.v.x", "vmfle.vv",
             "vmerge.vxm")},
    "vclt": {"shape": "vv->umask", "int": ("vmv.v.x", "vmslt.vv",
             "vmerge.vxm"), "uint": ("vmv.v.x", "vmsltu.vv",
             "vmerge.vxm"), "float": ("vmv.v.x", "vmflt.vv",
             "vmerge.vxm")},
    "vcle": {"shape": "vv->umask", "int": ("vmv.v.x", "vmsle.vv",
             "vmerge.vxm"), "uint": ("vmv.v.x", "vmsleu.vv",
             "vmerge.vxm"), "float": ("vmv.v.x", "vmfle.vv",
             "vmerge.vxm")},
    # lane-select: mask-register compare + merge (2 instrs, cheaper
    # than the cost model's 3-op bitwise estimate — the executed column
    # flags the divergence)
    "vbsl": {"shape": "vvv", "int": ("vmsne.vx", "vmerge.vvm"),
             "uint": ("vmsne.vx", "vmerge.vvm"),
             "float": ("vmsne.vx", "vmerge.vvm")},
    # broadcast / register moves
    "vdup": {"shape": "x", "int": ("vmv.v.x",), "uint": ("vmv.v.x",),
             "float": ("vfmv.v.f",)},
    "vtile": {"shape": "v", "int": ("vid.v", "vand.vx", "vrgather.vv"),
              "uint": ("vid.v", "vand.vx", "vrgather.vv"),
              "float": ("vid.v", "vand.vx", "vrgather.vv")},
    # register rearrangement (paper Listing 5)
    "vget_high": {"shape": "v", "int": ("vslidedown.vx",),
                  "uint": ("vslidedown.vx",),
                  "float": ("vslidedown.vx",)},
    "vget_low": {"shape": "v", "int": ("vmv.v.v",), "uint": ("vmv.v.v",),
                 "float": ("vmv.v.v",)},
    "vcombine": {"shape": "vv", "int": ("vmv.v.v", "vslideup.vx"),
                 "uint": ("vmv.v.v", "vslideup.vx"),
                 "float": ("vmv.v.v", "vslideup.vx")},
    # bit reverse (paper Listing 7: binary magic numbers, 15 instrs)
    "vrbit": {"shape": "v",
              "int": ("vsrl.vi", "vand.vx", "vand.vx", "vsll.vi",
                      "vor.vv") * 3,
              "uint": ("vsrl.vi", "vand.vx", "vand.vx", "vsll.vi",
                       "vor.vv") * 3},
    # reciprocal ladder: exact-division forms so the simulator matches
    # the logical ISA bit-for-bit (the logical vrecpe *is* 1/x)
    "vrecpe": {"shape": "v", "float": ("vfrdiv.vf",)},
    "vrecps": {"shape": "vv", "float": ("vfmul.vv", "vfrsub.vf")},
    "vrsqrte": {"shape": "v", "float": ("vfsqrt.v", "vfrdiv.vf")},
    "vrsqrts": {"shape": "vv", "float": ("vfmul.vv", "vfrsub.vf",
                                         "vfmul.vf")},
    # horizontal reductions (scalar init in element 0 of a scratch)
    "vaddv": {"shape": "v->x", "int": ("vmv.s.x", "vredsum.vs",
              "vmv.x.s"), "uint": ("vmv.s.x", "vredsum.vs", "vmv.x.s"),
              "float": ("vfmv.s.f", "vfredosum.vs", "vfmv.f.s")},
    "vmaxv": {"shape": "v->x", "int": ("vmv.x.s", "vmv.s.x",
              "vredmax.vs", "vmv.x.s"),
              "uint": ("vmv.x.s", "vmv.s.x", "vredmaxu.vs", "vmv.x.s"),
              "float": ("vfmv.f.s", "vfmv.s.f", "vfredmax.vs",
                        "vfmv.f.s")},
    "vminv": {"shape": "v->x", "int": ("vmv.x.s", "vmv.s.x",
              "vredmin.vs", "vmv.x.s"),
              "uint": ("vmv.x.s", "vmv.s.x", "vredminu.vs", "vmv.x.s"),
              "float": ("vfmv.f.s", "vfmv.s.f", "vfredmin.vs",
                        "vfmv.f.s")},
    # conversions
    "vcvt": {"shape": "v", "f->i": ("vfcvt.rtz.x.f.v",),
             "i->f": ("vfcvt.f.x.v",), "f->u": ("vfcvt.rtz.xu.f.v",),
             "u->f": ("vfcvt.f.xu.v",)},
    "vmovl": {"shape": "v", "int": ("vsext.vf2",),
              "uint": ("vzext.vf2",)},
    "vmovn": {"shape": "w", "int": ("vnsra.wi",), "uint": ("vnsrl.wi",)},
    "vqmovn": {"shape": "w", "int": ("vnclip.wi",),
               "uint": ("vnclipu.wi",)},
    "vqmovun": {"shape": "w", "int": ("vmax.vx", "vnclipu.wi")},
    # widening arithmetic (narrow SEW, 2x-EMUL destination)
    "vmull": {"shape": "vv", "int": ("vwmul.vv",),
              "uint": ("vwmulu.vv",)},
    "vaddl": {"shape": "vv", "int": ("vwadd.vv",),
              "uint": ("vwaddu.vv",)},
    "vsubl": {"shape": "vv", "int": ("vwsub.vv",),
              "uint": ("vwsubu.vv",)},
    "vmlal": {"shape": "vvv", "int": ("vwmacc.vv",),
              "uint": ("vwmaccu.vv",)},
    "vmlsl": {"shape": "vvv", "int": ("vwmul.vv", "vsub.vv"),
              "uint": ("vwmulu.vv", "vsub.vv")},
    # memory (unit-stride + segment families; masked forms reuse the
    # same access instruction under a cnt-element vsetvli, plus one
    # vmv.v.x building the tail-undisturbed fill register for loads)
    "vld1":  {"shape": "p", "any": ("vle<eew>.v",)},
    "vst1":  {"shape": "pv", "any": ("vse<eew>.v",)},
    "vld1m": {"shape": "p+cnt", "any": ("vmv.v.x", "vle<eew>.v",)},
    "vst1m": {"shape": "pv+cnt", "any": ("vse<eew>.v",)},
    # group-broadcast load (re-tiled walking vld1_dup): narrow vle of the
    # scalars, then a lane>>log2(reps) gather through an index register
    "vld1g":  {"shape": "p+g", "any": ("vle<eew>.v", "vid.v", "vsrl.vx",
                                       "vrgather.vv")},
    "vld1gm": {"shape": "p+g+cnt", "any": ("vmv.v.x", "vle<eew>.v",
                                           "vid.v", "vsrl.vx",
                                           "vrgather.vv")},
    # additive accumulator fold: halving vslidedown+add ladder
    "vfold": {"shape": "v", "int": ("vslidedown.vx", "vadd.vv"),
              "uint": ("vslidedown.vx", "vadd.vv"),
              "float": ("vslidedown.vx", "vfadd.vv")},
    "vld2":  {"shape": "p", "any": ("vlseg2e<eew>.v",)},
    "vst2":  {"shape": "pt", "any": ("vsseg2e<eew>.v",)},
    "vld2m": {"shape": "p+cnt", "any": ("vmv.v.x", "vlseg2e<eew>.v",)},
    "vst2m": {"shape": "pt+cnt", "any": ("vsseg2e<eew>.v",)},
    "vld3":  {"shape": "p", "any": ("vlseg3e<eew>.v",)},
    "vst3":  {"shape": "pt", "any": ("vsseg3e<eew>.v",)},
    "vld3m": {"shape": "p+cnt", "any": ("vmv.v.x", "vlseg3e<eew>.v",)},
    "vst3m": {"shape": "pt+cnt", "any": ("vsseg3e<eew>.v",)},
    "vld4":  {"shape": "p", "any": ("vlseg4e<eew>.v",)},
    "vst4":  {"shape": "pt", "any": ("vsseg4e<eew>.v",)},
    "vld4m": {"shape": "p+cnt", "any": ("vmv.v.x", "vlseg4e<eew>.v",)},
    "vst4m": {"shape": "pt+cnt", "any": ("vsseg4e<eew>.v",)},
    # free in the register file (no retired instruction)
    "vreinterpret": {"shape": "v", "any": ()},
    # scalar extract: slide the lane down, then move to x
    "vget_lane": {"shape": "v->x", "int": ("vslidedown.vx", "vmv.x.s"),
                  "uint": ("vslidedown.vx", "vmv.x.s"),
                  "float": ("vslidedown.vx", "vfmv.f.s")},
    # the fused requantization peephole: single-use vshr_n feeding a
    # saturating narrow collapses into one rounding narrow (RDN matches
    # C's arithmetic shift exactly); vqmovun keeps its vmax clamp
    "vshr_n+vqmovn": {"shape": "wx", "int": ("vnclip.wx",),
                      "uint": ("vnclipu.wx",)},
    "vshr_n+vqmovun": {"shape": "wx", "int": ("vmax.vx",
                                              "vnclipu.wx")},
}


def rvv_mnemonics(isa_op: str, dclass: str):
    """The RVV mnemonic expansion for one issue of ``isa_op`` on a
    ``dclass`` ("int"/"uint"/"float") operand, or None when the op has
    no registered RVV lowering (repro.rvv.codegen then raises)."""
    entry = RVV_MNEMONICS.get(isa_op)
    if entry is None:
        return None
    if "any" in entry:
        return entry["any"]
    return entry.get(dclass)
