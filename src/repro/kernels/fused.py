"""Fused division-step kernels: multiplication + glue in one launch.

The paper's cost model for the shifted-inverse Newton division counts
*multiplications only* because its CUDA implementation fuses everything
else -- carry resolution, shifts, precision/sign bookkeeping, the
PowDiff select -- into the same kernel that does the multiply.  The
JAX port previously ran only the products in Pallas; each Refine
iteration additionally issued ~15 separate XLA ops (associative carry
scans, `prec`, `shift`, `neg_mod_pow`, masked selects), every one a
full-width HBM round trip.  This module restores the paper's fusion:

  step_pallas     one Refine iteration (`shinv` Step, Algorithm 1) in
                  TWO batched Pallas launches: (1) PowDiff product +
                  sign/magnitude select, (2) w*x product + shift/add/
                  sub + floor correction + normalization shift +
                  active-instance select.
  correct_pallas  the `divmod_fixed` finalization (u*shinv >> h, v*q,
                  the delta in {-1,0,+1} compare-and-correct) in ONE
                  launch.
  barrett_pallas  `modarith.barrett_reduce`'s two truncated products +
                  two conditional subtracts in ONE launch.

Each kernel processes BLOCK_B instances per grid step (batch as the
leading grid axis, the paper's one-instance-per-CUDA-block schedule)
with the whole operand resident in VMEM; the glue arithmetic runs on
those tiles between the MXU products.  The `core.arith` primitives are
ported to Pallas-callable in-kernel forms below (`_k_*`): the
associative carry/borrow scans become Kogge-Stone ladders of log2(W)
lane rotates, dynamic limb shifts become conditional-rotate ladders
driven by the bits of the per-instance shift amount, and `prec` /
`take_limb` / comparisons become masked reductions -- no gathers, no
1-D iota, nothing the Mosaic lowering rejects.

TWO kernel generations implement each fused stage:

  * UNROLLED (`step_pallas` -> `_powdiff_kernel`/`_update_kernel`,
    `_correct_kernel`, `_barrett_kernel`): the whole block-pair
    product unrolled in one kernel body.  VMEM assumption: every
    operand, diagonal tile and glue temporary of BLOCK_B instances
    fits in one core's VMEM -- holds through ~2^13-bit operands.
  * GRID-SCHEDULED (`_powdiff_grid_kernel` etc.): the block-pair axis
    on the Pallas grid with a phase tape in SMEM, partial diagonals
    accumulated in a persistent VMEM scratch, and the glue applied in
    final revisit passes.  Compile time and per-step VMEM are O(1) in
    precision; this is how the paper's 2^15..2^18-bit Table 1 range
    runs fused.  See the grid section below for the full contract.

`kernels.ops.fused_path` dispatches between the generations by static
product geometry (threshold overridable); both share the `_*_glue`
bodies, so they are bit-identical by construction.

Launch-count contract (either generation, asserted in tests and the
div-smoke CI gate): one Refine iteration = FUSED_STEP_LAUNCHES = 2
pallas_calls, divmod finalization = 1, Barrett reduction = 1; a full
divmod_batch is 2*iters + 1 launches with ZERO full-width XLA glue
ops between them.

Kernel names (`pallas_call(name=...)`: the instruction's name in the
compiled program and the op's name in a device profile): by stage,
`refine_i<ii>_w<win>_powdiff` / `_update` for Refine iteration ii at
window win (`core/shinv.py:_refine`), `divmod_correct`, `barrett`,
each with `_grid` appended on the grid generation.

Zero-divisor contract (both generations, fused and reference):
divmod(u, 0) = (0, u) and shinv(0, h) = 0, applied inside
`_correct_glue`'s v == 0 select -- see core/shinv.py.

`step_reference` / `correct_reference` / `barrett_reference` are the
unfused compositions (K.mul products + core.arith glue in XLA) that
every other impl falls back to; `kernels.ops.fused_step` etc. own the
dispatch.  Bit-exactness of fused vs reference is asserted across the
whole windowed Refine schedule in tests/test_fused.py and
tests/test_grid_fused.py.

Off-TPU the kernels run in Pallas interpret mode (validation only; the
launch-count reduction is structural and backend-independent, see
benchmarks/div_breakdown.py).  On the TPU every product is a bf16 MXU
tile product with f32 accumulation (`bigmul._tile_dot`, exact for
sub-digit operands), and the carry and shift ladders are loops over a
traced lane rotate, which keeps the 2^18-bit kernels' compile time in
minutes.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.custom_batching
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bigint import MASK, DTYPE
from repro.core import arith as A
from . import ops as K
from .bigmul import _tile_dot, _toep_tile, _preresolve, pick_block_b
from .ops import BLOCK_T

_I = jnp.int32
_U = jnp.uint32

# Kernel-launch / glue-op accounting.  The numbers live in
# repro.obs.costmodel -- the single source of truth the measured-vs-
# model comparator predicts against -- and are re-exported here so the
# kernels' advertised contract can never drift from the model
# (serving.batching.kernel_plan and benchmarks/div_breakdown.py consume
# them from either name).
from repro.obs.costmodel import (          # noqa: E402  (re-export)
    FUSED_BARRETT_LAUNCHES, FUSED_CORRECT_LAUNCHES, FUSED_STEP_LAUNCHES,
    UNFUSED_STEP_GLUE_OPS)


def _rup(n: int, k: int) -> int:
    return -(-n // k) * k


def _iota(p: int) -> jax.Array:
    return jax.lax.broadcasted_iota(_I, (1, p), 1)


# ---------------------------------------------------------------------------
# in-kernel limb primitives (Pallas-callable ports of core.arith)
#
# All operate on (bb, P) int32 arrays of base-2^16 limbs at a padded
# static width P, with an explicit `width` argument reproducing the
# EXACT wrap/truncate semantics of the corresponding core.arith op at
# its unfused array width: operands are masked to `width` and results
# re-masked, so padding limbs never leak into the low `width` limbs
# (carries/borrows only travel upward).  Per-instance traced scalars
# arrive as (bb, 1) columns and broadcast.
# ---------------------------------------------------------------------------

def _k_msk(u: jax.Array, width) -> jax.Array:
    """u with limbs at index >= width zeroed (truncation to B^width)."""
    return jnp.where(_iota(u.shape[-1]) < width, u, 0)


def _k_scan(gen: jax.Array, prop: jax.Array) -> jax.Array:
    """Inclusive (generate, propagate) scan -> carry out of each limb.

    Kogge-Stone ladder of log2(P) rolls: the in-kernel form of
    `arith.carry_scan`'s associative scan (identity element (0, 1)).
    The levels run as a loop with a traced rotate amount, so the
    kernel holds one level's code, not log2(P) copies: at 2^18-bit
    widths the unrolled ladders dominated the Mosaic compile time."""
    p_ = gen.shape[-1]
    idx = _iota(p_)

    def level(k, gp):
        g, p = gp
        sft = jnp.left_shift(1, k)
        gs = jnp.where(idx >= sft, _k_roll(g, sft), 0)
        ps = jnp.where(idx >= sft, _k_roll(p, sft), 1)
        return g | (p & gs), p & ps

    g, _ = jax.lax.fori_loop(0, (p_ - 1).bit_length(), level, (gen, prop))
    return g


def _k_carry_in(gen: jax.Array, prop: jax.Array) -> jax.Array:
    """Exclusive form of `_k_scan`: carry INTO each limb."""
    g = _k_scan(gen, prop)
    return jnp.where(_iota(g.shape[-1]) >= 1, _k_roll(g, 1), 0)


def _k_add(u: jax.Array, v: jax.Array, width) -> jax.Array:
    """(u + v) mod B^width  (arith.add at array width `width`)."""
    s = u + v
    gen = (s >> 16).astype(_I)
    prop = ((s & MASK) == MASK).astype(_I)
    c = _k_carry_in(gen, prop)
    return _k_msk((s + c) & MASK, width)


def _k_sub(u: jax.Array, v: jax.Array, width) -> jax.Array:
    """(u - v) mod B^width  (arith.sub; exact when u >= v)."""
    d = u - v
    gen = (u < v).astype(_I)
    prop = (u == v).astype(_I)
    b = _k_carry_in(gen, prop)
    return _k_msk((d - b) & MASK, width)


def _k_lt(u: jax.Array, v: jax.Array) -> jax.Array:
    """u < v as a (bb, 1) bool column: the borrow OUT of the full
    subtraction (inclusive scan result at the top limb)."""
    gen = (u < v).astype(_I)
    prop = (u == v).astype(_I)
    g = _k_scan(gen, prop)
    return g[:, -1:] != 0


def _k_any(mask: jax.Array) -> jax.Array:
    """Row-wise any() as a (bb, 1) bool column, reduced in int32
    (Mosaic cannot truncate a reduced i8 back to i1)."""
    return jnp.max(mask.astype(_I), axis=-1, keepdims=True) != 0


def _k_is_zero(u: jax.Array) -> jax.Array:
    return ~_k_any(u != 0)


def _k_prec(u: jax.Array) -> jax.Array:
    """Significant-limb count as a (bb, 1) column (arith.prec)."""
    idx = _iota(u.shape[-1])
    return jnp.max(jnp.where(u != 0, idx + 1, 0), axis=-1, keepdims=True)


def _k_take(u: jax.Array, i) -> jax.Array:
    """u[i] with per-instance traced i; 0 out of range (arith.take_limb)."""
    return jnp.sum(jnp.where(_iota(u.shape[-1]) == i, u, 0),
                   axis=-1, keepdims=True)


def _k_roll(u: jax.Array, sft) -> jax.Array:
    """jnp.roll(u, sft, axis=-1) for 0 <= sft < P, sft static or traced
    (a lane rotate either way)."""
    return pltpu.roll(u, sft, u.ndim - 1)


def _k_shift(u: jax.Array, n, width) -> jax.Array:
    """Whole limb shift by n (arith.shift at array width `width`).

    Static python n: one roll.  Per-instance traced n (a (bb, 1)
    column): a loop of log2(P) conditional rolls driven by the bits
    of n mod P -- the in-kernel analogue of the host-side conditional-
    rotate Toeplitz staging.  The validity mask uses the UN-reduced n,
    so |n| >= width correctly yields zero."""
    p_ = u.shape[-1]
    idx = _iota(p_)
    if isinstance(n, int):
        r = _k_roll(u, n % p_) if n % p_ else u
    else:
        nn = jnp.remainder(n.astype(_I), p_)        # floor-mod -> [0, P)

        def bit(k, r):
            return jnp.where(((nn >> k) & 1) == 1,
                             _k_roll(r, jnp.left_shift(1, k)), r)

        r = jax.lax.fori_loop(0, (p_ - 1).bit_length(), bit, u)
    src = idx - n
    return jnp.where((src >= 0) & (src < width) & (idx < width), r, 0)


def _k_one_at(p_: int, i, width) -> jax.Array:
    """B^i as limbs at padded width p_ (bigint.one_hot_pow at `width`)."""
    idx = _iota(p_)
    return jnp.where((idx == i) & (idx < width), 1, 0)


def _k_neg_mod_pow(u: jax.Array, L, width) -> jax.Array:
    """B^L - u for 0 < u < B^L (arith.neg_mod_pow at width `width`)."""
    idx = _iota(u.shape[-1])
    comp = jnp.where((idx < L) & (idx < width), MASK - u, 0)
    return _k_add(comp, _k_one_at(u.shape[-1], 0, width), width)


def _k_sub_pow(u: jax.Array, p, width) -> jax.Array:
    """u - B^p, lowest-nonzero ripple decrement (arith.sub_pow)."""
    idx = _iota(u.shape[-1])
    cand = (u != 0) & (idx >= p)
    n = jnp.min(jnp.where(cand, idx, width), axis=-1, keepdims=True)
    dec = (idx >= p) & (idx <= n)
    return jnp.where(dec, (u - 1) & MASK, u)


# ---------------------------------------------------------------------------
# in-kernel multiplication: block-Toeplitz MXU products + full carry
# resolution, all on the VMEM-resident tiles
# ---------------------------------------------------------------------------

def _k_interleave(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Two (bb, T) digit rows -> (bb, 2T) with lo at even and hi at odd
    positions.  Mosaic has no lane-interleaving reshape, so this runs
    on the MXU against 0/1 spread matrices; it is exact because every
    output is a single digit < 2^8."""
    t = lo.shape[-1]
    r = jax.lax.broadcasted_iota(_I, (t, 2 * t), 0)
    c = jax.lax.broadcasted_iota(_I, (t, 2 * t), 1)
    out = (jnp.dot(lo.astype(jnp.bfloat16),
                   (c == 2 * r).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
           + jnp.dot(hi.astype(jnp.bfloat16),
                     (c == 2 * r + 1).astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32))
    return out.astype(_I)


def _k_split8(u: jax.Array, n8: int) -> jax.Array:
    """First n8 base-2^8 sub-digits of (bb, P) base-2^16 limbs, as
    (bb, n8).  P and n8 are multiples of BLOCK_T; sub-digits past the
    operand's P limbs are zero."""
    bb, p_ = u.shape
    t = BLOCK_T
    chunks = []
    for k in range(-(-n8 // (2 * t))):
        if k * t >= p_:
            chunks.append(jnp.zeros((bb, 2 * t), _I))
            continue
        c = u[:, k * t:(k + 1) * t]
        chunks.append(_k_interleave(c & 0xFF, (c >> 8) & 0xFF))
    return jnp.concatenate(chunks, axis=-1)[:, :n8]


def _k_deinterleave(x: jax.Array) -> jax.Array:
    """(bb, 2T) base-2^8 digits -> (bb, T) base-2^16 limbs.  The lane
    de-interleave runs on the MXU against 0/1 gather matrices (exact,
    one digit per output)."""
    t = x.shape[-1] // 2
    r = jax.lax.broadcasted_iota(_I, (2 * t, t), 0)
    c = jax.lax.broadcasted_iota(_I, (2 * t, t), 1)
    x = x.astype(jnp.bfloat16)
    lo = jnp.dot(x, (r == 2 * c).astype(jnp.bfloat16),
                 preferred_element_type=jnp.float32)
    hi = jnp.dot(x, (r == 2 * c + 1).astype(jnp.bfloat16),
                 preferred_element_type=jnp.float32)
    return lo.astype(_I) + (hi.astype(_I) << 8)


def _k_pack8(d: jax.Array) -> jax.Array:
    """(bb, n) base-2^8 digits, n a multiple of BLOCK_T -> (bb,
    ceil(n / 2T) * T) base-2^16 limbs."""
    bb, n = d.shape
    t = BLOCK_T
    if n % (2 * t):
        d = jnp.concatenate([d, jnp.zeros((bb, t), _I)], axis=-1)
    return jnp.concatenate(
        [_k_deinterleave(d[:, 2 * k * t:2 * (k + 1) * t])
         for k in range(d.shape[-1] // (2 * t))], axis=-1)


def _k_resolve8(raw: jax.Array) -> jax.Array:
    """Canonicalize raw sub-digit sums (< 2^31) to digits < 2^8: four
    local split passes then one Kogge-Stone carry scan (the in-kernel
    fusion of `ops._resolve8`)."""
    idx = _iota(raw.shape[-1])
    e = raw
    for _ in range(4):                      # carry magnitude /2^8 per pass
        d = e & 0xFF
        c = e >> 8
        e = d + jnp.where(idx >= 1, _k_roll(c, 1), 0)
    gen = e >> 8                            # in {0, 1}
    prop = ((e & 0xFF) == 0xFF).astype(_I)
    c = _k_carry_in(gen, prop)
    return (e + c) & 0xFF


def _k_mul(u: jax.Array, v: jax.Array, out_width: int, pg: int,
           cu: int | None = None, cv: int | None = None) -> jax.Array:
    """Exact (u * v) mod B^out_width on (bb, P) int32 limb tiles.

    The same block-Toeplitz schedule as `bigmul.mul_pallas_batched` --
    BLOCK_T-sized sub-digit tiles, Toeplitz staging by conditional
    rotates, diagonal pruning at d_keep = ceil(2*out_width / T) -- but
    unrolled INSIDE the kernel over the VMEM-resident operand, with the
    carry resolution fused immediately after, so the canonical product
    limbs are available in-register for the glue that follows.  Result
    is masked to out_width at padded width `pg`.

    cu/cv bound the operands' CONTENT width in limbs (they are masked
    to it by the caller); blocks past the content are all-zero and are
    pruned from the schedule structurally, like the unfused kernels'
    operand clipping.
    """
    bb = u.shape[0]
    t = BLOCK_T
    n8o = 2 * out_width                     # sub-digit positions kept
    d_keep = -(-n8o // t)
    n8k = min(2 * u.shape[-1], _rup(n8o, t))  # output clip: >= n8o is dead
    n8u = min(n8k, _rup(2 * (cu or pg), t))   # content clip: zeros beyond
    n8v = min(n8k, _rup(2 * (cv or pg), t))
    nu = n8u // t
    nv = n8v // t
    u8 = _k_split8(u, n8u)
    v8 = _k_split8(v, n8v)

    ndiag = min(nu + nv - 1, d_keep)
    n8r = (ndiag + 1) * t                   # top tile spills one block up
    segs = [None] * ndiag                   # per-diagonal (bb, 2t) sums
    for j in range(nv):
        toep = _toep_tile(v8[:, j * t:(j + 1) * t])          # (bb, t, 2t)
        for i in range(nu):
            d = i + j
            if d >= d_keep:
                continue
            prod = _tile_dot(u8[:, i * t:(i + 1) * t], toep)  # (bb, 2t)
            segs[d] = prod if segs[d] is None else segs[d] + prod
    # overlap-add of the (bb, 2t) diagonal tiles into (bb, n8r) raw
    # sums: tile d covers [d*t, d*t + 2t) -- pure concatenates, no
    # scatter (Pallas-lowerable)
    z = jnp.zeros((bb, t), _I)
    lo = jnp.concatenate([s[:, :t] for s in segs] + [z], axis=-1)
    hi = jnp.concatenate([z] + [s[:, t:] for s in segs], axis=-1)
    raw = lo + hi

    d8 = _k_resolve8(raw)
    d8 = jnp.where(_iota(n8r) < n8o, d8, 0)                  # mod B^out_width
    limbs = _k_pack8(d8)                                     # (bb, n8r//2)
    if limbs.shape[-1] < pg:
        limbs = jnp.concatenate(
            [limbs, jnp.zeros((bb, pg - limbs.shape[-1]), _I)], axis=-1)
    else:
        limbs = limbs[:, :pg]                # dropped limbs are >= out_width
    return _k_msk(limbs, out_width)


# ---------------------------------------------------------------------------
# glue bodies, shared between the unrolled and the grid-scheduled
# kernels.  Each takes the already-computed product limbs plus the
# VMEM-resident operands and performs everything AROUND the products;
# because both kernel generations call these exact functions, their
# bit-identity reduces to the exactness of the product itself.
# ---------------------------------------------------------------------------

def _powdiff_prologue(v, s, *, win, full_w):
    """Shifted-divisor prefix: shift(v, -s) truncated to the window."""
    return _k_msk(_k_shift(_k_msk(v, full_w), 0 - s, full_w), win)


def _powdiff_glue(p_, vp, wq, hpd, lpd, *, win: int, pg: int):
    """Algorithm-2 sign/magnitude select on the PowDiff product `p_`.

    Mirrors `_powdiff_reference` op for op; hpd/lpd carry the already-
    offset h-m and l-g columns.  Returns (sign int32 column, x)."""
    w2 = 2 * win
    idx = _iota(pg)
    pv = _k_prec(vp)
    pw = _k_prec(wq)
    L = pv + pw - lpd + 1
    vz = _k_is_zero(vp)
    wz = _k_is_zero(wq)
    full = vz | wz | (L >= hpd)
    # ---- full branch: compare p with B^h
    sign_full = _k_prec(p_) <= hpd
    mag_pos = _k_msk(_k_neg_mod_pow(p_, hpd, w2), win)
    mag_neg = _k_msk(_k_sub_pow(p_, hpd, w2), win)
    x_full = jnp.where(sign_full, mag_pos, mag_neg)
    x_full = jnp.where(vz | wz, _k_one_at(pg, hpd, win), x_full)
    # ---- close branch: P = (v*w) mod B^L, sign from top digit of P
    pc = jnp.where((idx < L) & (idx < win), p_, 0)           # mask_below[:win]
    pz = _k_is_zero(pc)
    ptop = _k_take(pc, L - 1)
    sign_close = pz | (ptop != 0)
    x_close = jnp.where(pz, jnp.zeros_like(pc),
                        jnp.where(ptop == 0, pc,
                                  _k_msk(_k_neg_mod_pow(pc, L, win), win)))

    # select in int32: Mosaic cannot select between i1 columns
    sign = jnp.where(full, sign_full.astype(_I), sign_close.astype(_I))
    x = jnp.where(full, x_full, x_close)
    return sign, x


def _update_glue(tmp, wq, w_full, sign, h, m, act, *, win: int, pg: int):
    """Shift/add/sub, floor correction, -1 normalization shift, and the
    active-instance select on the w*x product `tmp`."""
    idx = _iota(pg)
    w2 = 2 * win
    sh = _k_msk(_k_shift(tmp, 2 * m - h, w2), win)           # 2m-h <= 0 here
    wm = _k_shift(wq, m, win)
    res_pos = _k_add(wm, sh, win)
    res_neg = _k_sub(wm, sh, win)
    # floor correction: dropped limbs of tmp nonzero -> one more off
    drop = h - 2 * m
    dropped = _k_any((idx < drop) & (tmp != 0))
    one0 = _k_one_at(pg, 0, win)
    res_neg = jnp.where(dropped, _k_sub(res_neg, one0, win), res_neg)
    res = jnp.where(sign, res_pos, res_neg)
    res = _k_shift(res, -1, win)                             # normalization
    return jnp.where(act, res, w_full)


def _quotient_glue(p_, h, *, full_w: int):
    """q = floor(p_ / B^h) truncated to full_w -- the glue between the
    two products of both the divmod finalization and Barrett."""
    return _k_msk(_k_shift(p_, 0 - h, 2 * full_w), full_w)


def _correct_glue(u, v, q, mm, *, full_w: int, pg: int):
    """Algorithm-3 delta in {-1,0,+1} compare-and-correct, plus the
    documented total extension divmod(u, 0) = (0, u)."""
    one0 = _k_one_at(pg, 0, full_w)
    d_neg = _k_lt(u, mm)                     # delta = -1
    q = jnp.where(d_neg, _k_sub(q, one0, full_w), q)
    mm = jnp.where(d_neg, _k_sub(mm, v, full_w), mm)
    r = _k_sub(u, mm, full_w)
    d_pos = ~_k_lt(r, v)                     # delta = +1
    q = jnp.where(d_pos, _k_add(q, one0, full_w), q)
    r = jnp.where(d_pos, _k_sub(r, v, full_w), r)
    vz = _k_is_zero(v)
    return jnp.where(vz, jnp.zeros_like(q), q), jnp.where(vz, u, r)


def _barrett_glue(x, v, qv, *, full_w: int):
    """Barrett's two conditional subtracts (qhat error in {-1,0,+1})."""
    over = _k_lt(x, qv)                      # qhat = q+1
    qv = jnp.where(over, _k_sub(qv, v, full_w), qv)
    r = _k_sub(x, qv, full_w)
    under = ~_k_lt(r, v)                     # qhat = q-1
    return jnp.where(under, _k_sub(r, v, full_w), r)


# ---------------------------------------------------------------------------
# unrolled kernel bodies (whole operand in VMEM, block-pair product
# unrolled in-kernel -- the small/medium-precision fast path)
# ---------------------------------------------------------------------------

def _powdiff_kernel(v_ref, w_ref, h_ref, l_ref, s_ref, sign_ref, x_ref,
                    *, win: int, full_w: int, pg: int):
    """Launch 1 of a Refine iteration: shifted-divisor prologue, the
    PowDiff product, and the Algorithm-2 sign/magnitude select."""
    vp = _powdiff_prologue(v_ref[...], s_ref[...], win=win, full_w=full_w)
    wq = _k_msk(w_ref[...], win)
    p_ = _k_mul(vp, wq, 2 * win, pg, cu=win, cv=win)
    sign, x = _powdiff_glue(p_, vp, wq, h_ref[...], l_ref[...],
                            win=win, pg=pg)
    sign_ref[...] = sign
    x_ref[...] = x


def _update_kernel(w_ref, x_ref, sg_ref, h_ref, m_ref, a_ref, o_ref,
                   *, win: int, full_w: int, pg: int):
    """Launch 2 of a Refine iteration: the w*x product, shift/add/sub,
    floor correction, the -1 normalization shift, and the active-
    instance select back into the full-width iterate."""
    w_full = _k_msk(w_ref[...], full_w)
    wq = _k_msk(w_full, win)
    x = _k_msk(x_ref[...], win)
    tmp = _k_mul(wq, x, 2 * win, pg, cu=win, cv=win)
    o_ref[...] = _update_glue(tmp, wq, w_full, sg_ref[...] != 0,
                              h_ref[...], m_ref[...], a_ref[...] != 0,
                              win=win, pg=pg)


def _correct_kernel(u_ref, v_ref, si_ref, h_ref, q_ref, r_ref,
                    *, full_w: int, pg: int):
    """divmod finalization: q = floor(u*si / B^h), mm = v*q, then the
    delta in {-1,0,+1} compare-and-correct (Algorithm 3), plus the
    documented total extension divmod(u, 0) = (0, u)."""
    h = h_ref[...]
    u = _k_msk(u_ref[...], full_w)
    v = _k_msk(v_ref[...], full_w)
    si = _k_msk(si_ref[...], full_w)

    p_ = _k_mul(u, si, 2 * full_w, pg, cu=full_w, cv=full_w)  # double-prec
    q = _quotient_glue(p_, h, full_w=full_w)
    mm = _k_mul(v, q, full_w, pg, cu=full_w, cv=full_w)   # v*q fits full_w
    q, r = _correct_glue(u, v, q, mm, full_w=full_w, pg=pg)
    q_ref[...] = q
    r_ref[...] = r


def _barrett_kernel(x_ref, mu_ref, v_ref, r_ref, *, h: int, full_w: int,
                    pg: int):
    """Barrett reduction: two truncated products + two conditional
    subtracts at STATIC shift h (the cached-inverse hot path)."""
    x = _k_msk(x_ref[...], full_w)
    mu = _k_msk(mu_ref[...], full_w)
    v = _k_msk(v_ref[...], full_w)

    p_ = _k_mul(x, mu, 2 * full_w, pg, cu=full_w, cv=full_w)
    q = _quotient_glue(p_, h, full_w=full_w)
    qv = _k_mul(q, v, full_w, pg, cu=full_w, cv=full_w)
    r_ref[...] = _barrett_glue(x, v, qv, full_w=full_w)


# ---------------------------------------------------------------------------
# batched pallas_call plumbing + custom_vmap wrappers
# ---------------------------------------------------------------------------

def _pad2(a: jax.Array, p: int) -> jax.Array:
    """(batch, w) -> (batch, p) int32, zero-padded on the limb axis."""
    a = a.astype(_I)
    if a.shape[-1] < p:
        a = jnp.concatenate(
            [a, jnp.zeros((a.shape[0], p - a.shape[-1]), _I)], axis=-1)
    return a[:, :p]


def _col(a: jax.Array, batch: int) -> jax.Array:
    return jnp.reshape(a.astype(_I), (batch, 1))


def _fold(arrays, cols, pg: int, bb: int):
    """Kernel inputs: limb arrays padded to pg, per-instance scalars as
    columns, the batch padded to a multiple of bb and folded into
    (rows, bb, w).  The blocks squeeze the leading axis, so each
    block's last two dims equal the array's whatever bb is (Mosaic's
    (8, 128) block rule)."""
    batch = arrays[0].shape[0]
    bp = -(-batch // bb) * bb
    ins = [_pad2(a, pg) for a in arrays] + [_col(c, batch) for c in cols]
    if bp > batch:
        ins = [jnp.concatenate(
            [a, jnp.zeros((bp - batch,) + a.shape[1:], a.dtype)])
            for a in ins]
    return [a.reshape(bp // bb, bb, a.shape[-1]) for a in ins]


def _unfold(outs, batch: int, out_widths):
    """Kernel outputs (rows, bb, w) -> per-instance results: scalar
    columns as (batch,) int32, limb arrays trimmed to their width."""
    outs = outs if isinstance(outs, (list, tuple)) else (outs,)
    res = []
    for o, w in zip(outs, out_widths):
        o = o.reshape(-1, o.shape[-1])
        res.append(o[:batch, 0] if w == 1 else o[:batch, :w].astype(DTYPE))
    return res


def _launch(kernel, arrays, cols, out_widths, pg: int, name: str):
    """pallas_call a fused kernel, named `name` in the compiled program
    and in profiles, over the batch as the leading grid axis: BLOCK_B
    instances per step, whole (bb, pg) operands in VMEM, per-instance
    scalars as (bb, 1) columns."""
    batch = arrays[0].shape[0]
    bb = pick_block_b(batch)
    ins = _fold(arrays, cols, pg, bb)
    nr = ins[0].shape[0]
    in_specs = [pl.BlockSpec((None, bb, a.shape[-1]), lambda b: (b, 0, 0))
                for a in ins]
    widths = [1 if w == 1 else pg for w in out_widths]
    out_specs = [pl.BlockSpec((None, bb, w), lambda b: (b, 0, 0))
                 for w in widths]
    out_shape = [jax.ShapeDtypeStruct((nr, bb, w), _I) for w in widths]
    outs = pl.pallas_call(
        kernel,
        grid=(nr,),
        in_specs=in_specs,
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
        interpret=K.interpret_mode(),
        name=name,
    )(*ins)
    return _unfold(outs, batch, out_widths)


def _bcast(axis_size, in_batched, *args):
    return [a if b else jnp.broadcast_to(a, (axis_size,) + jnp.shape(a))
            for a, b in zip(args, in_batched)]


# ---------------------------------------------------------------------------
# grid-scheduled fused kernels (the paper's 2^15..2^18-bit range)
#
# The unrolled kernels above keep the whole block-pair product in one
# kernel body: nu*nv dot_generals unrolled at trace time with every
# diagonal tile live in VMEM.  That is the fast path through ~2^13-bit
# operands but both compile time and VMEM grow quadratically with
# precision.  The kernels below put the block-pair axis BACK on the
# Pallas grid (mirroring `bigmul.mul_pallas_batched` and the
# block-and-grid decomposition of Oancea & Watt 2024):
#
#   grid = (batch blocks, schedule steps); the schedule is a phase
#   tape in SMEM (scalar prefetch): one STAGE step splits the
#   VMEM-resident operands into sub-digit tiles held in scratch, each
#   PAIR step runs a bounded G x G block of BLOCK_T-tile MXU products
#   into a slab and accumulates pre-resolved partial diagonals into a
#   persistent VMEM scratch accumulator, and a final GLUE revisit pass
#   resolves the accumulator and applies the division glue (carry
#   ladders, shifts, PowDiff select, quotient correction) exactly as
#   the unrolled kernels do -- the glue bodies are shared functions.
#
# Launch count is unchanged (still ONE pallas_call per fused stage);
# what was an unrolled O(nu*nv) kernel body becomes an O(G^2) body
# executed over a grid, so compile time is O(1) in precision and the
# per-step VMEM product tile is bounded by G (<= MAX_GRID_G) BLOCK_T
# tiles.  The full-width operands and the accumulator still live in
# VMEM for the glue pass, so the batch block `bb` shrinks as precision
# grows (`_grid_block_b`) to keep the resident set inside the budget.
#
# Mosaic layout: the scratch tiles and the accumulator keep their tile
# index on the leading (untiled) axis, so the PAIR steps index them
# with traced scalars and no lane/sublane reshape is needed; the v5e
# compiler accepts every kernel here (tests/test_tpu_compile.py).
# ---------------------------------------------------------------------------

MAX_GRID_G = 16         # base tiles per super-tile axis (per-step bound)
GRID_TARGET_SUPERS = 36  # aim for <= this many super blocks per operand
# bytes of the `_grid_bytes` estimate per batch block.  The v5e Mosaic
# compiler allocated about twice the estimate (16.04 / 16.83 MB for
# the finalization / Refine kernels against an 8.4 MB estimate at 2^18
# bits, bb = 2), past its default 16 MiB scoped-VMEM limit, so the
# estimate may use a quarter of that limit.
GRID_VMEM_BUDGET = 4 << 20
GRID_LIMB_BUFS = 12     # VMEM accounting: full-width limb arrays live
GRID_GLUE_BUFS = 6      # ... and accumulator-width resolve temporaries

# phase tape opcodes
PH_STAGE, PH_PAIR1, PH_GLUE1, PH_PAIR2, PH_GLUE2 = range(5)

# revisit passes (non-PAIR phases) of the two-product finalization
# kernels (STAGE + GLUE1 + GLUE2); recorded in KernelPlan via
# `grid_plan`.  The single-product step kernels have one fewer.
GRID_CORRECT_PASSES = 3


def _prod_tiles(out_width: int, cu: int, cv: int) -> tuple[int, int, int]:
    """(nu, nv, d_keep) BLOCK_T-tile counts of the in-kernel product at
    out_width with operand content widths cu/cv -- exactly `_k_mul`'s
    clipping, so the unrolled and grid schedules cover the same pairs."""
    t = BLOCK_T
    n8o = 2 * out_width
    n8k = _rup(n8o, t)
    d_keep = -(-n8o // t)
    nu = min(n8k, _rup(2 * cu, t)) // t
    nv = min(n8k, _rup(2 * cv, t)) // t
    return nu, nv, d_keep


def _pick_g(out_width: int, cu: int, cv: int) -> int:
    """Super-tile factor G: smallest power of two keeping the operand
    axis at <= GRID_TARGET_SUPERS super blocks (so the schedule tape
    stays short), capped so the per-step slab stays bounded."""
    nu, nv, _ = _prod_tiles(out_width, cu, cv)
    g = 1
    while g < MAX_GRID_G and -(-max(nu, nv) // g) > GRID_TARGET_SUPERS:
        g *= 2
    return g


def _super_pairs(nu: int, nv: int, d_keep: int, g: int):
    """Diagonal-sorted (I, J) super pairs with (I+J)*g < d_keep, plus
    the super-axis sizes.  A kept super pair may contain pruned base
    pairs; their contributions land at sub-digit positions >= d_keep*t
    >= n8o and are masked by the final resolve, so no per-base masking
    is needed in-kernel."""
    nus, nvs = -(-nu // g), -(-nv // g)
    dks = -(-d_keep // g)
    pairs = [(i + j, i, j) for i in range(nus) for j in range(nvs)
             if i + j < dks]
    pairs.sort()
    return [(i, j) for _, i, j in pairs], nus, nvs, dks


def _grid_schedule(pairs1, pairs2=None):
    """Phase tape (phase, I, J) int32 arrays for one launch."""
    ph = [PH_STAGE] + [PH_PAIR1] * len(pairs1) + [PH_GLUE1]
    ii = [0] + [p[0] for p in pairs1] + [0]
    jj = [0] + [p[1] for p in pairs1] + [0]
    if pairs2 is not None:
        ph += [PH_PAIR2] * len(pairs2) + [PH_GLUE2]
        ii += [p[0] for p in pairs2] + [0]
        jj += [p[1] for p in pairs2] + [0]
    return (np.asarray(ph, np.int32), np.asarray(ii, np.int32),
            np.asarray(jj, np.int32))


def _grid_bytes(pg: int, sub_tiles: int, acc_elems: int) -> int:
    """Estimated VMEM bytes per batch-block instance: resident limb
    arrays + sub-digit operand scratch + accumulator and its resolve
    temporaries.  Coarse by design; consumed by `_grid_block_b`."""
    return 4 * (GRID_LIMB_BUFS * pg + sub_tiles * BLOCK_T
                + (1 + GRID_GLUE_BUFS) * acc_elems)


def _grid_block_b(batch: int, bytes_per_instance: int) -> int:
    """Instances per grid step: `pick_block_b`, halved until the
    VMEM-resident working set fits the budget (>= 1)."""
    bb = pick_block_b(batch)
    while bb > 1 and bb * bytes_per_instance > GRID_VMEM_BUDGET:
        bb //= 2
    return bb


def _lanes(k, t: int):
    """Lane offset k * t of chunk k, static or traced (then hinted as a
    multiple of t for Mosaic's aligned dynamic slicing)."""
    return k * t if isinstance(k, int) else pl.multiple_of(k * t, t)


def _stage8(ref, src_ref, width) -> None:
    """Split the limbs in `src_ref` (masked to `width`) into base-2^8
    sub-digits and store them into a (nb, bb, BLOCK_T) scratch tile
    ref, tile index leading so the PAIR steps index it dynamically on
    an untiled axis.  One 128-limb chunk (two tiles) per loop step, so
    the kernel holds one chunk's code at any precision.  Tiles beyond
    the operand content are zero; sub-digits beyond nb*BLOCK_T can only
    influence masked-out output positions (see `_super_pairs`)."""
    nb, _, t = ref.shape
    n_src = src_ref.shape[-1] // t
    lane = _iota(t)

    def chunk(k):
        off = _lanes(k, t)
        c = src_ref[:, pl.ds(off, t)]
        c = jnp.where(lane + off < width, c, 0)
        return _k_interleave(c & 0xFF, (c >> 8) & 0xFF)     # (bb, 2t)

    def body(k, carry):
        d = chunk(k)
        ref[2 * k] = d[:, :t]
        ref[2 * k + 1] = d[:, t:]
        return carry

    _zero(ref)
    jax.lax.fori_loop(0, min(nb // 2, n_src), body, 0)
    if nb % 2 and nb // 2 < n_src:                  # odd tail tile
        ref[nb - 1] = chunk(nb // 2)[:, :t]


def _grid_pair(a_ref, b_ref, acc_ref, slab_ref, i, j, *, g: int) -> None:
    """One PAIR step: the G x G base-tile MXU products of super pair
    (i, j) into a 3-super-tile slab (a (3g, bb, T) scratch, looped so
    the kernel holds one product's code), carry pre-resolution, then
    accumulation into the persistent diagonal accumulator.

    Slab overflow bound: a slab position receives <= 2g tile products
    of <= BLOCK_T * 255^2 each -- 2*16*128*255^2 < 2^28 < int31.  After
    `_preresolve` entries are <= 2^8+1, and an accumulator position
    collects <= 3 * min(nus, nvs) <= 108 of them: far inside int32, so
    the final `_k_resolve8` of the GLUE pass is exact."""
    t = BLOCK_T
    s_w = g * t

    def row(gj, carry):
        toep = _toep_tile(b_ref[j * g + gj])             # (bb, t, 2t)

        def col(gi, carry):
            prod = _tile_dot(a_ref[i * g + gi], toep)    # (bb, 2t)
            o = gi + gj
            slab_ref[o] = slab_ref[o] + prod[:, :t]
            slab_ref[o + 1] = slab_ref[o + 1] + prod[:, t:]
            return carry

        return jax.lax.fori_loop(0, g, col, carry)

    _zero(slab_ref)
    jax.lax.fori_loop(0, g, row, 0)
    slab = _preresolve(jnp.concatenate(
        [slab_ref[k] for k in range(3 * g)], axis=-1))
    d = i + j
    for k in range(3):
        acc_ref[d + k] = acc_ref[d + k] + slab[:, k * s_w:(k + 1) * s_w]


def _grid_resolve(acc_ref, lb_ref, out_width: int) -> jax.Array:
    """Final carry resolution of the whole (ns, bb, s_w) accumulator
    -> canonical product limbs masked to out_width at the padded width
    of the (bb, pg) scratch `lb_ref` (the exact tail of `_k_mul`).  The
    resolved digits go back into the accumulator and are packed into
    limbs one 2T-digit chunk per loop step."""
    ns, _, s_w = acc_ref.shape
    t = BLOCK_T
    g = s_w // t
    raw = jnp.concatenate([acc_ref[k] for k in range(ns)], axis=-1)
    d8 = _k_resolve8(raw)
    d8 = jnp.where(_iota(raw.shape[-1]) < 2 * out_width, d8, 0)
    for k in range(ns):
        acc_ref[k] = d8[:, k * s_w:(k + 1) * s_w]

    def chunk(c, carry):
        halves = [acc_ref[q // g, :, pl.ds(_lanes(q % g, t), t)]
                  for q in (2 * c, 2 * c + 1)]
        lb_ref[:, pl.ds(_lanes(c, t), t)] = _k_deinterleave(
            jnp.concatenate(halves, axis=-1))
        return carry

    _zero(lb_ref)
    jax.lax.fori_loop(0, min(ns * g // 2, lb_ref.shape[-1] // t), chunk, 0)
    return _k_msk(lb_ref[...], out_width)


def _zero(ref) -> None:
    ref[...] = jnp.zeros(ref.shape, _I)


# ---- grid kernel bodies ---------------------------------------------------

def _powdiff_grid_kernel(ph_ref, i_ref, j_ref,
                         v_ref, w_ref, h_ref, l_ref, s_ref,
                         sign_ref, x_ref,
                         a8_ref, b8_ref, acc_ref, slab_ref, lb_ref,
                         *, win: int, full_w: int, pg: int, g: int):
    """Grid-scheduled launch 1 of a Refine iteration."""
    p = pl.program_id(1)
    ph = ph_ref[p]

    @pl.when(ph == PH_STAGE)
    def _():
        lb_ref[...] = _powdiff_prologue(v_ref[...], s_ref[...], win=win,
                                        full_w=full_w)
        _stage8(a8_ref, lb_ref, win)
        _stage8(b8_ref, w_ref, win)
        _zero(acc_ref)

    @pl.when(ph == PH_PAIR1)
    def _():
        _grid_pair(a8_ref, b8_ref, acc_ref, slab_ref, i_ref[p], j_ref[p],
                   g=g)

    @pl.when(ph == PH_GLUE1)
    def _():
        vp = _powdiff_prologue(v_ref[...], s_ref[...], win=win,
                               full_w=full_w)
        wq = _k_msk(w_ref[...], win)
        p_ = _grid_resolve(acc_ref, lb_ref, 2 * win)
        sign, x = _powdiff_glue(p_, vp, wq, h_ref[...], l_ref[...],
                                win=win, pg=pg)
        sign_ref[...] = sign
        x_ref[...] = x


def _update_grid_kernel(ph_ref, i_ref, j_ref,
                        w_ref, x_ref, sg_ref, h_ref, m_ref, a_ref,
                        o_ref,
                        a8_ref, b8_ref, acc_ref, slab_ref, lb_ref,
                        *, win: int, full_w: int, pg: int, g: int):
    """Grid-scheduled launch 2 of a Refine iteration."""
    p = pl.program_id(1)
    ph = ph_ref[p]

    @pl.when(ph == PH_STAGE)
    def _():
        _stage8(a8_ref, w_ref, win)
        _stage8(b8_ref, x_ref, win)
        _zero(acc_ref)

    @pl.when(ph == PH_PAIR1)
    def _():
        _grid_pair(a8_ref, b8_ref, acc_ref, slab_ref, i_ref[p], j_ref[p],
                   g=g)

    @pl.when(ph == PH_GLUE1)
    def _():
        w_full = _k_msk(w_ref[...], full_w)
        wq = _k_msk(w_full, win)
        tmp = _grid_resolve(acc_ref, lb_ref, 2 * win)
        o_ref[...] = _update_glue(tmp, wq, w_full, sg_ref[...] != 0,
                                  h_ref[...], m_ref[...], a_ref[...] != 0,
                                  win=win, pg=pg)


def _correct_grid_kernel(ph_ref, i_ref, j_ref,
                         u_ref, v_ref, si_ref, h_ref,
                         q_ref, r_ref,
                         a8_ref, b8_ref, c8_ref, q8_ref, qs_ref, acc_ref,
                         slab_ref, lb_ref,
                         *, full_w: int, pg: int, g: int):
    """Grid-scheduled divmod finalization: product u*si, quotient glue,
    product v*q, compare-and-correct -- two pair phases, the second's
    Toeplitz operand staged from the first's GLUE revisit."""
    p = pl.program_id(1)
    ph = ph_ref[p]

    @pl.when(ph == PH_STAGE)
    def _():
        _stage8(a8_ref, u_ref, full_w)
        _stage8(b8_ref, si_ref, full_w)
        _stage8(c8_ref, v_ref, full_w)
        _zero(acc_ref)

    @pl.when(ph == PH_PAIR1)
    def _():
        _grid_pair(a8_ref, b8_ref, acc_ref, slab_ref, i_ref[p], j_ref[p],
                   g=g)

    @pl.when(ph == PH_GLUE1)
    def _():
        p_ = _grid_resolve(acc_ref, lb_ref, 2 * full_w)
        qs_ref[...] = _quotient_glue(p_, h_ref[...], full_w=full_w)
        _stage8(q8_ref, qs_ref, full_w)
        _zero(acc_ref)

    @pl.when(ph == PH_PAIR2)
    def _():
        _grid_pair(c8_ref, q8_ref, acc_ref, slab_ref, i_ref[p], j_ref[p],
                   g=g)

    @pl.when(ph == PH_GLUE2)
    def _():
        u = _k_msk(u_ref[...], full_w)
        v = _k_msk(v_ref[...], full_w)
        mm = _grid_resolve(acc_ref, lb_ref, full_w)
        q, r = _correct_glue(u, v, qs_ref[...], mm, full_w=full_w, pg=pg)
        q_ref[...] = q
        r_ref[...] = r


def _barrett_grid_kernel(ph_ref, i_ref, j_ref,
                         x_ref, mu_ref, v_ref,
                         r_ref,
                         a8_ref, b8_ref, c8_ref, q8_ref, qs_ref, acc_ref,
                         slab_ref, lb_ref,
                         *, h: int, full_w: int, pg: int, g: int):
    """Grid-scheduled Barrett reduction (static shift h)."""
    p = pl.program_id(1)
    ph = ph_ref[p]

    @pl.when(ph == PH_STAGE)
    def _():
        _stage8(a8_ref, x_ref, full_w)
        _stage8(b8_ref, mu_ref, full_w)
        _stage8(c8_ref, v_ref, full_w)
        _zero(acc_ref)

    @pl.when(ph == PH_PAIR1)
    def _():
        _grid_pair(a8_ref, b8_ref, acc_ref, slab_ref, i_ref[p], j_ref[p],
                   g=g)

    @pl.when(ph == PH_GLUE1)
    def _():
        p_ = _grid_resolve(acc_ref, lb_ref, 2 * full_w)
        qs_ref[...] = _quotient_glue(p_, h, full_w=full_w)
        _stage8(q8_ref, qs_ref, full_w)
        _zero(acc_ref)

    @pl.when(ph == PH_PAIR2)
    def _():
        _grid_pair(c8_ref, q8_ref, acc_ref, slab_ref, i_ref[p], j_ref[p],
                   g=g)

    @pl.when(ph == PH_GLUE2)
    def _():
        x = _k_msk(x_ref[...], full_w)
        v = _k_msk(v_ref[...], full_w)
        qv = _grid_resolve(acc_ref, lb_ref, full_w)
        r_ref[...] = _barrett_glue(x, v, qv, full_w=full_w)


def _launch_grid(kernel, sched, arrays, cols, out_widths, pg: int,
                 scratch_fn, bytes_per_instance: int, name: str):
    """pallas_call a grid-scheduled fused kernel named `name`: grid =
    (batch blocks, phase-tape steps), full-width operands resident per
    batch block (index maps constant over the step axis), the tape in
    SMEM via scalar prefetch, operand tiles / accumulator in VMEM
    scratch."""
    batch = arrays[0].shape[0]
    bb = _grid_block_b(batch, bytes_per_instance)
    ins = _fold(arrays, cols, pg, bb)
    nr = ins[0].shape[0]
    ph, ii, jj = sched
    in_specs = [pl.BlockSpec((None, bb, a.shape[-1]),
                             lambda b, p, ph, i, j: (b, 0, 0))
                for a in ins]
    widths = [1 if w == 1 else pg for w in out_widths]
    out_specs = [pl.BlockSpec((None, bb, w), lambda b, p, ph, i, j: (b, 0, 0))
                 for w in widths]
    out_shape = [jax.ShapeDtypeStruct((nr, bb, w), _I) for w in widths]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nr, len(ph)),
        in_specs=in_specs,
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        scratch_shapes=scratch_fn(bb),
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
        interpret=K.interpret_mode(),
        name=name,
    )(jnp.asarray(ph), jnp.asarray(ii), jnp.asarray(jj), *ins)
    return _unfold(outs, batch, out_widths)


def _as_cv(batched, n_out: int):
    """custom_vmap wrapper factory: single instances take the
    batch-of-1 path; `jax.vmap` hands the whole batch to `batched`."""
    @jax.custom_batching.custom_vmap
    def f(*args):
        outs = batched(*(a[None] for a in args))
        outs = outs if isinstance(outs, tuple) else (outs,)
        res = tuple(o[0] for o in outs)
        return res if n_out > 1 else res[0]

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        outs = batched(*_bcast(axis_size, in_batched, *args))
        return outs, ((True,) * n_out if n_out > 1 else True)

    return f


@functools.lru_cache(maxsize=None)
def _powdiff_cv(win: int, full_w: int, pg: int, name: str):
    kern = functools.partial(_powdiff_kernel, win=win, full_w=full_w, pg=pg)

    def batched(v, w, hpd, lpd, s):
        sign, x = _launch(kern, (v, w), (hpd, lpd, s), (1, full_w), pg,
                          name)
        return sign != 0, x

    @jax.custom_batching.custom_vmap
    def f(v, w, hpd, lpd, s):
        sign, x = batched(v[None], w[None], hpd[None], lpd[None], s[None])
        return sign[0], x[0]

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        return batched(*_bcast(axis_size, in_batched, *args)), (True, True)

    return f


@functools.lru_cache(maxsize=None)
def _update_cv(win: int, full_w: int, pg: int, name: str):
    kern = functools.partial(_update_kernel, win=win, full_w=full_w, pg=pg)

    def batched(w, x, sign, h, m, act):
        (out,) = _launch(kern, (w, x), (sign, h, m, act), (full_w,), pg,
                         name)
        return out

    @jax.custom_batching.custom_vmap
    def f(w, x, sign, h, m, act):
        return batched(w[None], x[None], sign[None], h[None], m[None],
                       act[None])[0]

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        return batched(*_bcast(axis_size, in_batched, *args)), True

    return f


@functools.lru_cache(maxsize=None)
def _correct_cv(full_w: int, pg: int):
    kern = functools.partial(_correct_kernel, full_w=full_w, pg=pg)

    def batched(u, v, si, h):
        q, r = _launch(kern, (u, v, si), (h,), (full_w, full_w), pg,
                       "divmod_correct")
        return q, r

    @jax.custom_batching.custom_vmap
    def f(u, v, si, h):
        q, r = batched(u[None], v[None], si[None], h[None])
        return q[0], r[0]

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        return batched(*_bcast(axis_size, in_batched, *args)), (True, True)

    return f


@functools.lru_cache(maxsize=None)
def _barrett_cv(full_w: int, pg: int, h: int):
    kern = functools.partial(_barrett_kernel, h=h, full_w=full_w, pg=pg)

    def batched(x, mu, v):
        (r,) = _launch(kern, (x, mu, v), (), (full_w,), pg, "barrett")
        return r

    @jax.custom_batching.custom_vmap
    def f(x, mu, v):
        return batched(x[None], mu[None], v[None])[0]

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        return batched(*_bcast(axis_size, in_batched, *args)), True

    return f


# ---------------------------------------------------------------------------
# grid-scheduled custom_vmap builders (cached per static geometry)
# ---------------------------------------------------------------------------

def _step_grid_geom(win: int):
    """Shared geometry of both Refine-step products (out 2*win,
    content win x win): (g, pairs, tile counts, acc tiles)."""
    g = _pick_g(2 * win, win, win)
    nu, nv, dk = _prod_tiles(2 * win, win, win)
    pairs, nus, nvs, dks = _super_pairs(nu, nv, dk, g)
    return g, pairs, nus * g, nvs * g, dks + 2


def _correct_grid_geom(full_w: int):
    """Geometry of the two-product finalization kernels: product 1 is
    u*si at out 2*full_w (it fixes G and the accumulator size), product
    2 is v*q at out full_w on the same G."""
    g = _pick_g(2 * full_w, full_w, full_w)
    nu1, nv1, dk1 = _prod_tiles(2 * full_w, full_w, full_w)
    pairs1, nus1, nvs1, dks1 = _super_pairs(nu1, nv1, dk1, g)
    nu2, nv2, dk2 = _prod_tiles(full_w, full_w, full_w)
    pairs2, nus2, nvs2, _ = _super_pairs(nu2, nv2, dk2, g)
    return (g, pairs1, pairs2, nus1 * g, nvs1 * g, nus2 * g, nvs2 * g,
            dks1 + 2)


def grid_plan(full_w: int) -> tuple[int, int, int]:
    """(schedule steps, super tile in sub-digits, revisit passes) of
    the grid-scheduled finalization kernel at width full_w -- the
    geometry single source for serving.batching.KernelPlan."""
    g, pairs1, pairs2, *_ = _correct_grid_geom(full_w)
    steps = len(pairs1) + len(pairs2) + GRID_CORRECT_PASSES
    return steps, g * BLOCK_T, GRID_CORRECT_PASSES


def correct_dispatch(full_w: int) -> tuple[str, int]:
    """(fused generation, padded width pg) the finalization kernel at
    width full_w will actually use -- the SAME derivation as
    `correct_pallas`/`barrett_pallas`, exported so KernelPlan and the
    benchmarks report the dispatch the kernel performs rather than
    re-deriving it."""
    pg = _rup(2 * full_w, BLOCK_T)
    return K.fused_path(2 * full_w, full_w, full_w, pg), pg


@functools.lru_cache(maxsize=None)
def _powdiff_grid_cv(win: int, full_w: int, pg: int, name: str):
    g, pairs, nba, nbb, ns = _step_grid_geom(win)
    s_w = g * BLOCK_T
    sched = _grid_schedule(pairs)
    kern = functools.partial(_powdiff_grid_kernel, win=win, full_w=full_w,
                             pg=pg, g=g)
    bpi = _grid_bytes(pg, nba + nbb + 3 * g, ns * s_w)

    def scratch(bb):
        return [pltpu.VMEM((nba, bb, BLOCK_T), _I),
                pltpu.VMEM((nbb, bb, BLOCK_T), _I),
                pltpu.VMEM((ns, bb, s_w), _I),
                pltpu.VMEM((3 * g, bb, BLOCK_T), _I),
                pltpu.VMEM((bb, pg), _I)]

    def batched(v, w, hpd, lpd, s):
        sign, x = _launch_grid(kern, sched, (v, w), (hpd, lpd, s),
                               (1, full_w), pg, scratch, bpi, name)
        return sign != 0, x

    return _as_cv(batched, 2)


@functools.lru_cache(maxsize=None)
def _update_grid_cv(win: int, full_w: int, pg: int, name: str):
    g, pairs, nba, nbb, ns = _step_grid_geom(win)
    s_w = g * BLOCK_T
    sched = _grid_schedule(pairs)
    kern = functools.partial(_update_grid_kernel, win=win, full_w=full_w,
                             pg=pg, g=g)
    bpi = _grid_bytes(pg, nba + nbb + 3 * g, ns * s_w)

    def scratch(bb):
        return [pltpu.VMEM((nba, bb, BLOCK_T), _I),
                pltpu.VMEM((nbb, bb, BLOCK_T), _I),
                pltpu.VMEM((ns, bb, s_w), _I),
                pltpu.VMEM((3 * g, bb, BLOCK_T), _I),
                pltpu.VMEM((bb, pg), _I)]

    def batched(w, x, sign, h, m, act):
        (out,) = _launch_grid(kern, sched, (w, x), (sign, h, m, act),
                              (full_w,), pg, scratch, bpi, name)
        return out

    return _as_cv(batched, 1)


def _two_product_scratch(full_w: int, pg: int):
    """Scratch builder + byte estimate shared by the correct/Barrett
    grid kernels (a8, b8, c8, q8, q-limbs, acc)."""
    g, pairs1, pairs2, nba, nbb, nbc, nbq, ns = _correct_grid_geom(full_w)
    s_w = g * BLOCK_T
    sched = _grid_schedule(pairs1, pairs2)
    bpi = _grid_bytes(pg, nba + nbb + nbc + nbq + 3 * g, ns * s_w) + 4 * pg

    def scratch(bb):
        return [pltpu.VMEM((nba, bb, BLOCK_T), _I),
                pltpu.VMEM((nbb, bb, BLOCK_T), _I),
                pltpu.VMEM((nbc, bb, BLOCK_T), _I),
                pltpu.VMEM((nbq, bb, BLOCK_T), _I),
                pltpu.VMEM((bb, pg), _I),
                pltpu.VMEM((ns, bb, s_w), _I),
                pltpu.VMEM((3 * g, bb, BLOCK_T), _I),
                pltpu.VMEM((bb, pg), _I)]

    return g, sched, scratch, bpi


@functools.lru_cache(maxsize=None)
def _correct_grid_cv(full_w: int, pg: int):
    g, sched, scratch, bpi = _two_product_scratch(full_w, pg)
    kern = functools.partial(_correct_grid_kernel, full_w=full_w, pg=pg,
                             g=g)

    def batched(u, v, si, h):
        q, r = _launch_grid(kern, sched, (u, v, si), (h,),
                            (full_w, full_w), pg, scratch, bpi,
                            "divmod_correct_grid")
        return q, r

    return _as_cv(batched, 2)


@functools.lru_cache(maxsize=None)
def _barrett_grid_cv(full_w: int, pg: int, h: int):
    g, sched, scratch, bpi = _two_product_scratch(full_w, pg)
    kern = functools.partial(_barrett_grid_kernel, h=h, full_w=full_w,
                             pg=pg, g=g)

    def batched(x, mu, v):
        (r,) = _launch_grid(kern, sched, (x, mu, v), (), (full_w,), pg,
                            scratch, bpi, "barrett_grid")
        return r

    return _as_cv(batched, 1)


# ---------------------------------------------------------------------------
# public fused entry points (per-instance; batch via jax.vmap -- the
# custom_vmap rules route whole batches into single launches).  Each
# picks the unrolled or the grid-scheduled kernel generation via
# `kernels.ops.fused_path` (size-based dispatch, threshold
# overridable); both generations share the glue bodies and are
# bit-identical.
# ---------------------------------------------------------------------------

def step_pallas(v, w, *, h, m, l, s, active, g: int, win: int,
                name: str = "refine"):
    """One Refine iteration in two batched Pallas launches, named
    `<name>_powdiff` and `<name>_update` (`_grid` appended on the grid
    generation)."""
    full_w = v.shape[-1]
    pg = max(_rup(2 * win, BLOCK_T), _rup(full_w, BLOCK_T))
    grid = K.fused_path(2 * win, win, win, pg) == "grid"
    gen = "_grid" if grid else ""
    pd_cv = (_powdiff_grid_cv if grid else _powdiff_cv)(
        win, full_w, pg, f"{name}_powdiff{gen}")
    up_cv = (_update_grid_cv if grid else _update_cv)(
        win, full_w, pg, f"{name}_update{gen}")
    hpd = jnp.asarray(h - m, _I)
    lpd = jnp.asarray(l - g, _I)
    sign, x = pd_cv(v, w, hpd, lpd, jnp.asarray(s, _I))
    return up_cv(
        w, x, jnp.asarray(sign, _I), jnp.asarray(h, _I), jnp.asarray(m, _I),
        jnp.asarray(active, _I))


def correct_pallas(u, v, si, *, h):
    """divmod finalization in one batched Pallas launch -> (q, r)."""
    full_w = u.shape[-1]
    path, pg = correct_dispatch(full_w)
    cv = (_correct_grid_cv if path == "grid" else _correct_cv)(full_w, pg)
    q, r = cv(u, v, si, jnp.asarray(h, _I))
    return q, r


def barrett_pallas(x, mu, v, *, h: int):
    """Barrett reduction core in one batched Pallas launch -> r."""
    full_w = mu.shape[-1]
    path, pg = correct_dispatch(full_w)
    cv = (_barrett_grid_cv(full_w, pg, h) if path == "grid"
          else _barrett_cv(full_w, pg, h))
    return cv(x, mu, v)


# ---------------------------------------------------------------------------
# reference compositions (the unfused fallback: K.mul products + XLA
# glue).  These are the former shinv._powdiff / shinv._step bodies and
# the divmod_fixed / barrett_reduce tails, verbatim; the fused kernels
# above are asserted bit-identical to them in tests/test_fused.py.
# ---------------------------------------------------------------------------

def _powdiff_reference(v, w, h, l, *, width, impl):
    """(sign, x = |B^h - v*w|) per Algorithm 2.  v, w: (width,) limbs.

    One full product serves both the full and the close branch (the
    close product only saves work at the kernel level; the Pallas
    mulmod kernel skips high blocks when the static window allows it).
    """
    w2 = 2 * width
    pv, pw = A.prec(v), A.prec(w)
    L = pv + pw - l + 1
    p = K.mul(v, w, w2, impl=impl)

    full = A.is_zero(v) | A.is_zero(w) | (L >= h)
    # ---- full branch: compare p with B^h
    sign_full = A.prec(p) <= h               # p < B^h  (p == B^h -> mag 0)
    mag_pos = A.neg_mod_pow(p, h)[:width]    # B^h - p   (needs p < B^h)
    mag_neg = A.sub_pow(p, h)[:width]        # p - B^h   (Listing 1.3)
    x_full = jnp.where(sign_full, mag_pos, mag_neg)
    x_full = jnp.where(A.is_zero(v) | A.is_zero(w),
                       _one_hot(h, width), x_full)           # |B^h - 0|
    # ---- close branch: P = (v*w) mod B^L, sign from top digit of P
    P = A.mask_below(p, L)[:width]
    p_zero = A.is_zero(P)
    p_top = A.take_limb(P, L - 1)
    sign_close = p_zero | (p_top != 0)
    x_close = jnp.where(p_zero, jnp.zeros((width,), _U),
                        jnp.where(p_top == 0, P, A.neg_mod_pow(P, L)[:width]))

    sign = jnp.where(full, sign_full, sign_close)
    x = jnp.where(full, x_full, x_close)
    return sign, x


def _one_hot(p, m):
    idx = jnp.arange(m, dtype=_I)
    return jnp.where(idx == p, _U(1), _U(0))


def step_reference(v, w, *, h, m, l, s, active, g: int, win: int, impl):
    """One Refine iteration as the unfused composition (Algorithm 1
    Step, floor-exact, plus the prologue shift, the -1 normalization
    and the active-instance select)."""
    width = v.shape[-1]
    w2 = 2 * win
    v_pre = A.shift(v, -s)[:win]
    wq = w[:win]
    sign, x = _powdiff_reference(v_pre, wq, h - m, l - g, width=win,
                                 impl=impl)
    tmp = K.mul(wq, x, w2, impl=impl)
    sh = A.shift(tmp, 2 * m - h)[:win]        # 2m-h <= 0 always here
    wm = A.shift(wq, m)
    res_pos = A.add(wm, sh)
    res_neg = A.sub(wm, sh)
    # floor correction: dropped limbs of tmp nonzero -> one more off
    drop = h - 2 * m
    idx = jnp.arange(w2, dtype=_I)
    dropped_nz = jnp.any((idx < drop) & (tmp != 0))
    res_neg = jnp.where(dropped_nz, A.sub_scalar(res_neg, 1), res_neg)
    w_new = jnp.where(sign, res_pos, res_neg)
    w_new = A.shift(w_new, -1)
    if win < width:
        w_new = jnp.concatenate(
            [w_new, jnp.zeros((width - win,), w_new.dtype)])
    return jnp.where(active, w_new, w)


def correct_reference(u, v, si, *, h, impl):
    """Algorithm 3 finalization with the revised delta in {-1, 0, +1}
    correction; divmod(u, 0) = (0, u) by the documented contract."""
    width = u.shape[-1]
    p = K.mul(u, si, 2 * width, impl=impl)   # double-precision product
    q = A.shift(p, -h)[:width]
    mm = K.mul(v, q, width, impl=impl)       # v*q fits width

    d_neg = A.lt(u, mm)                      # delta = -1
    q = jnp.where(d_neg, A.sub_scalar(q, 1), q)
    mm = jnp.where(d_neg, A.sub(mm, v), mm)
    r = A.sub(u, mm)
    d_pos = A.ge(r, v)                       # delta = +1
    q = jnp.where(d_pos, A.add_scalar(q, 1), q)
    r = jnp.where(d_pos, A.sub(r, v), r)
    vz = A.is_zero(v)
    q = jnp.where(vz, jnp.zeros_like(q), q)
    r = jnp.where(vz, u, r)
    return q, r


def barrett_reference(x, mu, v, *, h, impl):
    """Two truncated products + two conditional subtracts (the
    barrett_reduce tail; qhat error in {-1, 0, +1})."""
    width = x.shape[-1]
    p = K.mul(x, mu, 2 * width, impl=impl)
    q = A.shift(p, -h)[:width]
    qv = K.mul(q, v, width, impl=impl)

    over = A.lt(x, qv)                       # qhat = q+1
    qv = jnp.where(over, A.sub(qv, v), qv)
    r = A.sub(x, qv)
    under = A.ge(r, v)                       # qhat = q-1
    r = jnp.where(under, A.sub(r, v), r)
    return r
