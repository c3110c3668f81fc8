"""Whole-shifted-inverse division in JAX (Algorithms 1-3 of the paper).

Single-instance functions over fixed-width limb vectors; batch with
`jax.vmap`, distribute with pjit (see repro.launch / repro.serving).

JAX adaptation notes (vs. the CUDA implementation in the paper):

  * Fixed shapes: CUDA dispatches variable-size multiplications to
    statically specialized kernels at runtime.  Tracing requires static
    shapes, so v1 executes every Refine iteration at full width W and
    masks inactive instances; the size-bucketed variant (static window
    per unrolled iteration, mirroring the paper's effMul<BLOCK, Q>
    specialization) is the `windowed=True` path -- see EXPERIMENTS.md
    SPerf for the measured effect.
  * The Refine loop has a static trip count ceil(log2(M)) + 2 (the
    paper's own fixed-count formulation, line 19 of Algorithm 1) and is
    unrolled at trace time; per-instance convergence is handled with
    `where` masks, exactly like warp-divergence-free SIMD execution.
  * Scalar bookkeeping (h, k, l, m, s, g) are traced int32 scalars.
  * The initial 4-by-2-digit quotient B^3 quo V is computed exactly in
    uint32 (no 64-bit hardware integers on TPU): one wrap-around 32/32
    division plus a 16-step restoring division, all vectorizable.
  * Multiplications dispatch through `K.mul`, which is batch-aware:
    with `impl="pallas_batched"` a `custom_vmap` rule hands each whole
    vmapped batch to the natively batched Pallas kernel --
    `divmod_batch` and every windowed Refine product launch one kernel
    per multiplication, not one per batch lane.
  * The per-iteration arithmetic itself lives behind the fused
    division-step registry (`K.fused_step` / `K.fused_correct`,
    kernels/fused.py): with `impl="pallas_fused"` (the TPU default)
    one Refine iteration compiles to TWO batched Pallas launches with
    all glue (carry scans, shifts, prec, PowDiff select, floor
    correction) executed in-kernel, and the divmod finalization to
    ONE; other impls run the reference composition (K.mul products +
    arith glue in XLA, ~15 full-width ops per step).  Both paths are
    bit-identical (tests/test_fused.py).
  * Launch-count contract: `divmod_batch(impl="pallas_fused")` is
    exactly 2 * refine_iters(m) + 1 pallas_calls at EVERY precision --
    below ~2^13-bit operands the fused kernels unroll their products
    in-kernel, above that the same launches run grid-scheduled with a
    bounded per-step VMEM tile (kernels/ops.fused_path dispatches;
    tests/test_grid_fused.py asserts the contract on both
    generations).

Sign handling and the delta in {-1,0,+1} quotient correction follow the
paper's revised Theorem 2.

Zero-divisor contract: division by zero is defined as the total
extension divmod(u, 0) = (0, u), and shinv_fixed(0, h) = 0.  See
`_initial_w0` for how the v == 0 lane is masked through the traced
(branch-free) refinement.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .bigint import BASE, LOG_BASE, MASK, DTYPE, one_hot_pow
from . import arith as A
from repro.kernels import ops as K

_U = jnp.uint32
_I = jnp.int32

GUARD = 2   # guard digits g (paper: Refine line 16)
PAD = 8     # extra limbs of internal headroom above M


def refine_iters(m_limbs: int) -> int:
    """Static Refine trip count for an m-limb division (the paper's
    fixed-count formulation, Algorithm 1 line 19).  Single source of
    truth -- benchmarks/div_breakdown.py and tests derive their
    launch-count contracts (2 launches * this + 1) from it."""
    return math.ceil(math.log2(max(m_limbs, 2))) + 2


def _initial_w0(V: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Exact floor(B^3 / V) for V in [B, B^2), as three base-B limbs.

    q1 = floor(2^32 / V) via wrap-around uint32 division;
    q2 = floor((2^32 mod V) * 2^16 / V) via 16-step restoring division.

    The `maximum(V, 1)` below is NOT silent zero-divisor handling: it
    only keeps the traced uint32 division well-defined on the v == 0
    lane of a batch (integer division by zero is backend-dependent in
    XLA).  The seed it produces there is garbage by design --
    `shinv_fixed` masks the v == 0 lane to the documented result 0
    after refinement, and `divmod_fixed` maps it to (q, r) = (0, u)
    (see the module docstring; asserted in tests/test_fused.py).
    """
    V = jnp.maximum(V, _U(1))
    q1 = (_U(0) - V) // V + _U(1)            # floor(2^32 / V), exact
    r1 = _U(0) - q1 * V                      # 2^32 - q1*V (mod 2^32), < V
    t = r1
    q2 = _U(0)
    for _ in range(LOG_BASE):
        ovf = t >= _U(1 << 31)
        t = t << 1                           # wraps; ovf remembers bit 32
        geq = ovf | (t >= V)
        t = jnp.where(geq, t - V, t)         # wrap-correct when ovf
        q2 = (q2 << 1) | geq.astype(_U)
    # w0 = q1 * B + q2  (q1 <= 2^16, so three limbs suffice)
    return q2 & _U(MASK), q1 & _U(MASK), q1 >> LOG_BASE


def _refine(v, h, k, w, *, width, iters_max, impl, windowed=True):
    """Guarded shorter-iterate/divisor-prefix refinement loop.

    windowed=True is the JAX analogue of the paper's statically
    specialized variable-size multiplications (effMul<BLOCK, q>):
    iteration i provably satisfies l <= 2^i + 1, so all its operands
    fit a static window of 2^(i+1)+16 limbs; each unrolled iteration
    traces its multiplications at that width.  Work becomes a geometric
    series sum_i (2^i)^2 ~ (4/3) M^2 instead of log2(M) * M^2, which is
    what restores the paper's 5-7 full-multiplication cost model.
    (Size-bound proof sketch: the full PowDiff branch only triggers for
    l <= g+3 where indices are < 32; the close branch bounds every
    value by B^L with L <= 2l+2g+2 < window; the w*x product fits the
    doubled window since 3*2^i+12 < 4*2^i+32.)

    Each iteration runs through `K.fused_step` (the prologue shift,
    PowDiff + select, w*x update, floor correction, -1 normalization
    and active-instance select): two batched Pallas launches under
    impl="pallas_fused", the reference composition elsewhere.
    """
    g = GUARD
    l = jnp.asarray(2, _I)
    w = A.shift(w, g)
    hk = h - k
    need = jnp.where(hk - 1 >= 2, A.ceil_log2(jnp.maximum(hk - 1, 1)),
                     0) + 2
    for i in range(iters_max):
        wi = min(max(32, 2 ** (i + 1) + 16), width) if windowed else width
        active = i < need
        m = jnp.clip(jnp.minimum(hk + 1 - l, l), 0, None)
        s = jnp.maximum(0, k - 2 * l + 1 - g)
        w = K.fused_step(v, w, h=k + l + m - s + g, m=m, l=l, s=s,
                         active=active, g=g, win=wi, impl=impl,
                         name=f"refine_i{i:02d}_w{wi}")
        l = jnp.where(active, l + m - 1, l)
    return A.shift(w, h - k - l - g)


def shinv_fixed(v: jax.Array, h: jax.Array, *, iters_max: int,
                impl: str | None = None,
                windowed: bool = True) -> jax.Array:
    """shinv_h(v) + lambda, lambda in {0,1} (Theorem 2). v: (W,) limbs,
    h: int32 scalar (may be traced).

    Contract at v == 0: returns 0 (there is no finite floor(B^h / 0);
    0 is the fixed point that makes `divmod_fixed` total -- see the
    module docstring)."""
    width = v.shape[0]
    h = jnp.asarray(h, _I)

    # lift single-limb v: floor(B^(h+1) / vB) == floor(B^h / v)
    small = A.prec(v) <= 1
    v_eff = jnp.where(small, A.shift(v, 1), v)
    h_eff = h + small.astype(_I)
    k = A.prec(v_eff) - 1

    # ---- special cases (guarantee B < v <= B^h / 2 for the general path)
    two_v = A.add(v_eff, v_eff)
    case_zero = A.gt_pow(v_eff, h_eff)                   # v >  B^h -> 0
    case_one = A.gt_pow(two_v, h_eff) & ~case_zero       # 2v > B^h -> 1
    case_pow = A.is_pow(v_eff)                           # v == B^k -> B^(h-k)

    # ---- initial approximation from the two most significant limbs
    V = A.take_limb(v_eff, k - 1) + (A.take_limb(v_eff, k) << LOG_BASE)
    d0, d1, d2 = _initial_w0(V)
    w0 = jnp.zeros((width,), _U).at[0].set(d0).at[1].set(d1).at[2].set(d2)

    w = _refine(v_eff, h_eff, k, w0, width=width, iters_max=iters_max,
                impl=impl, windowed=windowed)

    w = jnp.where(case_pow, one_hot_pow(h_eff - k, width), w)
    w = jnp.where(case_one, one_hot_pow(0, width), w)
    w = jnp.where(case_zero, jnp.zeros((width,), _U), w)
    # v == 0: the masked _initial_w0 seed refined garbage; define the
    # result as 0 (documented zero-divisor contract)
    w = jnp.where(A.is_zero(v), jnp.zeros((width,), _U), w)
    return w


def divmod_fixed(u: jax.Array, v: jax.Array,
                 impl: str | None = None,
                 windowed: bool = True) -> tuple[jax.Array, jax.Array]:
    """(q, r) with u = q*v + r, 0 <= r < v.  u, v: (M,) limb vectors.

    Algorithm 3 with the revised delta in {-1, 0, +1} correction; the
    finalization (u*shinv >> h, v*q, compare-and-correct) runs through
    `K.fused_correct` -- one batched Pallas launch under
    impl="pallas_fused".

    Zero-divisor contract: divmod_fixed(u, 0) = (0, u) (total
    extension; both fused and reference paths implement it).
    """
    m_limbs = u.shape[0]
    width = m_limbs + PAD
    iters_max = refine_iters(m_limbs)
    uw = jnp.zeros((width,), _U).at[:m_limbs].set(u.astype(_U))
    vw = jnp.zeros((width,), _U).at[:m_limbs].set(v.astype(_U))

    h = A.prec(uw)
    si = shinv_fixed(vw, h, iters_max=iters_max, impl=impl,
                     windowed=windowed)
    q, r = K.fused_correct(uw, vw, si, h=h, impl=impl)
    return q[:m_limbs], r[:m_limbs]


@partial(jax.jit, static_argnames=("impl", "windowed"))
def divmod_batch(u: jax.Array, v: jax.Array, impl: str | None = None,
                 windowed: bool = True):
    """Batched division: u, v of shape (batch, M).

    With `impl="pallas_batched"` every internal multiplication runs as
    ONE natively batched kernel launch over the whole batch (the
    custom_vmap rule in kernels/ops.py), not a per-lane grid.  With
    `impl="pallas_fused"` the glue arithmetic fuses in too: the whole
    batched division is 2 launches per Refine iteration plus 1 for the
    finalization -- nothing else touches the limbs from XLA."""
    return jax.vmap(
        lambda a, b: divmod_fixed(a, b, impl=impl, windowed=windowed)
    )(u, v)


@partial(jax.jit, static_argnames=("iters_max", "impl", "windowed"))
def shinv_batch(v: jax.Array, h: jax.Array, iters_max: int,
                impl: str | None = None, windowed: bool = True):
    """Batched whole shifted inverse: v (batch, W), h (batch,)."""
    return jax.vmap(
        lambda vv, hh: shinv_fixed(vv, hh, iters_max=iters_max, impl=impl,
                                   windowed=windowed)
    )(v, h)
