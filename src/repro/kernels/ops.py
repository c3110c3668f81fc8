"""Jit-ready multiplication entry points with implementation dispatch.

Four interchangeable implementations of the classical (quadratic)
multi-precision product:

  * "scan"    -- digit-loop oracle (ref.py).  Exact, sequential, slow.
  * "blocked" -- block-Toeplitz integer matmul (this file).  The limbs
                 are split into base-2^8 sub-digits so every partial
                 product fits int32; the convolution becomes a batch of
                 (T x 2T) integer matmuls followed by an anti-diagonal
                 segment-sum.  This is the TPU-native adaptation of the
                 paper's register-tiled CUDA schedule: the MXU consumes
                 the Toeplitz tiles, carries are resolved afterwards by
                 one associative scan (base-2^8, 4 local passes).
  * "pallas"  -- single-instance Pallas kernel with explicit VMEM
                 BlockSpec tiling (kernels/bigmul.py), same math as
                 "blocked"; batches via the generic vmap rule.
  * "pallas_batched"
              -- natively batched Pallas kernel (kernels/bigmul.py,
                 `mul_pallas_batched`): the batch is a leading grid
                 axis (one instance per grid row, the paper's
                 one-instance-per-CUDA-block schedule), Toeplitz tiles
                 are staged *inside* the kernel from the raw sub-digit
                 operand block (no host-side (nv, t, 2t) gather), and
                 carry pre-resolution is fused into the kernel epilogue
                 so only a short 2-pass + associative-scan fixup
                 remains in XLA.  `mul` under `jax.vmap` routes whole
                 batches to this kernel through a `custom_vmap` rule,
                 so `divmod_batch` / `barrett_reduce` / the windowed
                 Refine pay one kernel launch per product, not one per
                 batch lane.

  * "pallas_fused"
              -- same batched multiplication kernel, plus FUSED
                 division-step kernels (kernels/fused.py): the glue
                 arithmetic around each product of the shifted-inverse
                 Newton iteration (carry scans, shifts, prec, PowDiff
                 sign/magnitude select, quotient correction) executes
                 in-kernel on the VMEM-resident tiles, so one Refine
                 iteration is 2 launches and the divmod / Barrett
                 finalizations are 1 launch each (see `fused_step`,
                 `fused_correct`, `fused_barrett` at the bottom).
                 Within this impl, `fused_path` auto-dispatches each
                 kernel between the UNROLLED generation (whole product
                 in one kernel body; VMEM assumption: ~2^13-bit
                 operands max) and the GRID-SCHEDULED generation (pair
                 axis on the Pallas grid, bounded per-step tile; the
                 paper's 2^15..2^18-bit range) -- launch counts are
                 identical, the threshold is overridable via
                 `set_fused_grid_threshold`.

All are exact and validated against each other in tests.  Default
dispatch: "pallas_fused" on TPU, "blocked" elsewhere (fast on CPU,
where Pallas runs in interpret mode); `set_default_impl` overrides.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.custom_batching
import jax.numpy as jnp

from repro.core.bigint import LOG_BASE, MASK
from repro.core.arith import carry_scan, mask_below
from . import ref as _ref

_U = jnp.uint32
_I = jnp.int32

# Block size of the Toeplitz tiles, in base-2^8 sub-digits.  128 keeps
# MXU dims hardware-aligned (128x256 tiles) while bounding the
# anti-diagonal accumulation well inside int32.
BLOCK_T = 128

IMPLS = ("scan", "blocked", "pallas", "pallas_batched", "pallas_fused")

# Resolved lazily so importing this module never forces backend init;
# None means "pallas_fused on TPU, blocked elsewhere".
DEFAULT_IMPL: str | None = None


def default_impl() -> str:
    global DEFAULT_IMPL
    if DEFAULT_IMPL is None:
        DEFAULT_IMPL = ("pallas_fused"
                        if jax.default_backend() == "tpu" else "blocked")
    return DEFAULT_IMPL


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in interpret mode: everywhere
    but on a TPU backend (CPU validation).  Read at trace time; a test
    that compiles the kernels for a described TPU patches it."""
    return jax.default_backend() != "tpu"


def set_default_impl(name: str) -> None:
    global DEFAULT_IMPL
    if name not in IMPLS:
        raise ValueError(f"unknown impl {name!r}; expected one of {IMPLS}")
    DEFAULT_IMPL = name


# ---------------------------------------------------------------------------
# graceful-degradation ladder (consumed by the serving tier)
#
# Every impl is bit-exact against every other (CI-enforced), so when a
# Pallas compile or launch fails at some (impl, bucket, precision) the
# serving frontend can fall DOWN this ladder and still return exactly
# the bytes the healthy path would: each step trades launches/perf for
# a strictly simpler lowering (fused kernels -> plain batched kernel
# -> pure-XLA blocked matmul, which needs no Mosaic at all).  "scan"
# is deliberately not a fallback target: it is the test oracle, orders
# of magnitude too slow to serve traffic.
# ---------------------------------------------------------------------------

_FALLBACK = {"pallas_fused": "pallas_batched",
             "pallas_batched": "blocked",
             "pallas": "blocked"}


def fallback_impl(name: str) -> str | None:
    """The next impl down the degradation ladder, or None when `name`
    is terminal ("blocked"/"scan" run as plain XLA ops)."""
    if name not in IMPLS:
        raise ValueError(f"unknown impl {name!r}; expected one of {IMPLS}")
    return _FALLBACK.get(name)


def fallback_chain(name: str) -> list[str]:
    """`name` followed by every impl below it on the ladder."""
    chain = [name]
    nxt = fallback_impl(name)
    while nxt is not None:
        chain.append(nxt)
        nxt = _FALLBACK.get(nxt)
    return chain


# ---------------------------------------------------------------------------
# fused-kernel generation dispatch (unrolled vs grid-scheduled)
#
# The fused division-step kernels come in two generations
# (kernels/fused.py): the UNROLLED kernels keep the whole block-pair
# product in one kernel body (fast through ~2^13-bit operands; compile
# time and VMEM grow quadratically with precision), the GRID-SCHEDULED
# kernels put the pair axis on the Pallas grid with a scratch diagonal
# accumulator and a final glue revisit pass (O(1) compile, bounded
# per-step VMEM -- the paper's 2^15..2^18-bit range).  `fused_path`
# picks per static product geometry; both generations are bit-exact,
# so the choice is purely a compile-time/VMEM tradeoff.
# ---------------------------------------------------------------------------

# Unrolled-path ceilings, derived from hardware budgets:
#  * pairs: every (i, j) block pair is a dot_general unrolled in the
#    kernel body; past ~256 the Mosaic compile time dominates.
#  * VMEM: the unrolled body keeps every v tile's (T, 2T) bf16
#    Toeplitz tile live, plus ~12 full-width limb arrays and ~6
#    sub-digit-width arrays (operands, diagonal tiles, resolve
#    temporaries) per instance, and the batched launch runs up to
#    MAX_BLOCK_B = 16 instances per grid step.  The v5e Mosaic
#    compiler's scoped-VMEM need at bb = 16 was 3.15 / 8.98 / 15.88 /
#    22.11 MB at 264 / 520 / 776 / 1024-limb widths, against its
#    default 16 MiB limit; the estimate may use three quarters of it.
FUSED_UNROLL_MAX_PAIRS = 256
FUSED_VMEM_BUDGET = 12 << 20
_FUSED_LIMB_BUFS = 12
_FUSED_SUB_BUFS = 6

# Manual override: None = derive from the budgets above; an int makes
# the decision a pure out_width cutoff (out_width > threshold -> grid),
# which tests use to exercise the grid kernels at tiny sizes.
_FUSED_GRID_THRESHOLD: int | None = None


def set_fused_grid_threshold(out_limbs: int | None) -> None:
    """Override the unrolled->grid dispatch: products with out_width >
    out_limbs take the grid-scheduled kernels.  None restores the
    automatic VMEM/compile-time derivation.

    Changing the threshold clears jax's compilation caches: the
    dispatch is resolved at trace time, so executables traced under
    the previous threshold would otherwise keep their old kernel
    generation on cache hits (same shapes/statics)."""
    global _FUSED_GRID_THRESHOLD
    if out_limbs != _FUSED_GRID_THRESHOLD:
        _FUSED_GRID_THRESHOLD = out_limbs
        jax.clear_caches()


def fused_grid_threshold() -> int | None:
    return _FUSED_GRID_THRESHOLD


def fused_path(out_width: int, cu: int, cv: int, pg: int) -> str:
    """"unrolled" or "grid" for a fused kernel whose dominant product
    is (cu x cv limbs) truncated to out_width, padded to pg limbs.

    Counts the dot_generals the unrolled body would emit from the same
    tile derivation the kernels use (`fused._prod_tiles`, the `_k_mul`
    clipping/pruning schedule), and estimates its VMEM-resident bytes
    at the maximum batch block; either budget overrun dispatches to
    the grid generation.
    """
    if _FUSED_GRID_THRESHOLD is not None:
        return "grid" if out_width > _FUSED_GRID_THRESHOLD else "unrolled"
    from . import bigmul, fused
    t = BLOCK_T
    nu, nv, d_keep = fused._prod_tiles(out_width, cu, cv)
    pairs = sum(max(0, min(nv, d_keep - i)) for i in range(nu))
    if pairs > FUSED_UNROLL_MAX_PAIRS:
        return "grid"
    n8r = (min(nu + nv - 1, d_keep) + 1) * t
    est = bigmul.MAX_BLOCK_B * (nv * 2 * t * 2 * t       # bf16 Toeplitz
                                + 4 * (_FUSED_LIMB_BUFS * pg
                                       + _FUSED_SUB_BUFS * n8r))
    return "grid" if est > FUSED_VMEM_BUDGET else "unrolled"


# ---------------------------------------------------------------------------
# base-2^8 sub-digit helpers
# ---------------------------------------------------------------------------

def _to_u8digits(u: jax.Array) -> jax.Array:
    """(..., W) base-2^16 limbs -> (..., 2W) base-2^8 sub-digits
    (still uint32).  Operates on the last axis."""
    lo = u & _U(0xFF)
    hi = (u >> 8) & _U(0xFF)
    return jnp.stack([lo, hi], axis=-1).reshape(u.shape[:-1] + (-1,))


def _resolve8(raw: jax.Array, passes: int = 4) -> jax.Array:
    """Canonicalize base-2^8 raw sums to sub-digits < 2^8 (last axis).

    `passes` local split passes shrink the carry magnitude by 2^8 each
    before the (generate, propagate) scan finishes: raw sums < 2^31
    need the default 4; kernel-pre-resolved sums (< 2^10, see
    bigmul.mul_pallas_batched) need only 2.
    """
    idx = jnp.arange(raw.shape[-1], dtype=_I)

    def shift1(c):
        r = jnp.roll(c, 1, axis=-1)
        return jnp.where(idx >= 1, r, _U(0))

    e = raw
    for _ in range(passes):                 # carry magnitude /2^8 per pass
        d = e & _U(0xFF)
        c = e >> 8
        e = d + shift1(c)
    gen = (e >> 8).astype(_I)               # in {0,1}
    prop = ((e & _U(0xFF)) == _U(0xFF)).astype(_I)
    carry = carry_scan(gen, prop, axis=-1).astype(_U)
    return (e + carry) & _U(0xFF)


def _pack8(d8: jax.Array) -> jax.Array:
    """(..., 2W) base-2^8 digits -> (..., W) base-2^16 limbs."""
    pairs = d8.reshape(d8.shape[:-1] + (-1, 2))
    return pairs[..., 0] | (pairs[..., 1] << 8)


# ---------------------------------------------------------------------------
# blocked Toeplitz matmul product
# ---------------------------------------------------------------------------

def _toeplitz_blocks(v8: jax.Array, nb: int, t: int) -> jax.Array:
    """(nb*t,) -> (nb, t, 2t) where Toep[j, c, s] = v8[j*t + s - c]."""
    # guard-pad so gather indices are always in range
    vg = jnp.concatenate([jnp.zeros((t,), _I), v8.astype(_I),
                          jnp.zeros((t,), _I)])
    j = jnp.arange(nb, dtype=_I)[:, None, None]
    c = jnp.arange(t, dtype=_I)[None, :, None]
    s = jnp.arange(2 * t, dtype=_I)[None, None, :]
    idx = j * t + s - c + t                  # +t for the guard pad
    tile = jnp.take(vg, idx, axis=0)
    # restrict to THIS block's sub-digits: 0 <= s-c < t (otherwise the
    # neighbouring block's pair (i, j+1) would count the product twice)
    return jnp.where((s - c >= 0) & (s - c < t), tile, 0)


def _mul_blocked(u: jax.Array, v: jax.Array, out_width: int) -> jax.Array:
    """Pair-list block-Toeplitz product with diagonal pruning.

    The product is truncated mod B^out_width, so any block pair whose
    diagonal d = i+j starts at or beyond 2*out_width sub-digits cannot
    contribute: those pairs are pruned from the schedule *structurally*
    (fewer batched matmuls, not a mask).  This is the paper's
    close-product (MULTMOD) work saving generalized to every truncated
    multiplication -- e.g. the W-truncated v*q in Algorithm 3 skips
    half its pairs.
    """
    t = BLOCK_T
    wo8 = 2 * out_width
    u8 = _to_u8digits(u.astype(_U))[: wo8]     # limbs >= wo8 can't matter
    v8 = _to_u8digits(v.astype(_U))[: wo8]
    nu = max(-(-u8.shape[0] // t), 1)
    nv = max(-(-v8.shape[0] // t), 1)
    u8 = jnp.zeros((nu * t,), _U).at[: u8.shape[0]].set(u8)
    v8 = jnp.zeros((nv * t,), _U).at[: v8.shape[0]].set(v8)

    d_keep = -(-wo8 // t)                      # pair kept iff i+j < d_keep
    pairs = [(i, j) for i in range(nu) for j in range(nv)
             if i + j < d_keep]
    i_idx = jnp.asarray([p[0] for p in pairs], _I)
    j_idx = jnp.asarray([p[1] for p in pairs], _I)
    diag = jnp.asarray([p[0] + p[1] for p in pairs], _I)

    ub = u8.reshape(nu, t).astype(_I)                    # (nu, t)
    toep = _toeplitz_blocks(v8, nv, t)                   # (nv, t, 2t)
    up = jnp.take(ub, i_idx, axis=0)                     # (P, t)
    tp = jnp.take(toep, j_idx, axis=0)                   # (P, t, 2t)
    prods = jnp.einsum("pc,pcs->ps", up, tp,
                       preferred_element_type=_I)        # (P, 2t)
    nseg = min(nu + nv - 1, d_keep)
    seg = jax.ops.segment_sum(prods, diag, num_segments=nseg)
    n8 = (nseg + 1) * t
    raw = jnp.zeros((n8,), _I)
    raw = raw.at[: nseg * t].add(seg[:, :t].reshape(-1))
    raw = raw.at[t:].add(seg[:, t:].reshape(-1))
    raw = raw.astype(_U)

    if n8 < wo8:
        raw = jnp.concatenate([raw, jnp.zeros((wo8 - n8,), _U)])
    else:
        raw = raw[:wo8]
    return _pack8(_resolve8(raw))


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _mul_pallas_batched_cv(out_width: int):
    """custom_vmap wrapper: single instances take the batch-of-1 path;
    `jax.vmap` hands the WHOLE batch to the natively batched kernel in
    one launch (batch = leading grid axis) instead of adding a lane per
    instance.  Cached per static out_width so repeated traces reuse one
    wrapper (and its vmap rule)."""
    from . import bigmul

    @jax.custom_batching.custom_vmap
    def _mul_pb(u, v):
        return bigmul.mul_pallas_batched(u[None, :], v[None, :],
                                         out_width)[0]

    @_mul_pb.def_vmap
    def _mul_pb_vmap(axis_size, in_batched, u, v):
        ub, vb = in_batched
        if not ub:
            u = jnp.broadcast_to(u, (axis_size,) + u.shape)
        if not vb:
            v = jnp.broadcast_to(v, (axis_size,) + v.shape)
        return bigmul.mul_pallas_batched(u, v, out_width), True

    return _mul_pb


def mul(u: jax.Array, v: jax.Array, out_width: int,
        impl: str | None = None) -> jax.Array:
    """Exact u*v truncated (mod) to out_width limbs. Single instance;
    vmap for batches ("pallas_batched" routes whole vmapped batches to
    one natively batched kernel launch)."""
    impl = impl or default_impl()
    if impl == "scan":
        return _ref.mul_ref(u, v, out_width)
    if impl == "blocked":
        return _mul_blocked(u, v, out_width)
    if impl == "pallas":
        from . import bigmul
        return bigmul.mul_pallas(u, v, out_width)
    if impl in ("pallas_batched", "pallas_fused"):
        # "pallas_fused" only changes the DIVISION-STEP entry points
        # (fused_step / fused_correct / fused_barrett below); a bare
        # product is the same natively batched kernel either way.
        return _mul_pallas_batched_cv(out_width)(u, v)
    raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")


def mul_batch(u: jax.Array, v: jax.Array, out_width: int,
              impl: str | None = None) -> jax.Array:
    """Batched product: u, v (batch, W) -> (batch, out_width).

    "pallas_batched" dispatches the batch natively (one kernel launch,
    batch as the leading grid axis); other impls fall back to vmap.
    """
    impl = impl or default_impl()
    if impl in ("pallas_batched", "pallas_fused"):
        from . import bigmul
        return bigmul.mul_pallas_batched(u, v, out_width)
    return jax.vmap(lambda a, b: mul(a, b, out_width, impl=impl))(u, v)


def mulmod(u: jax.Array, v: jax.Array, L, out_width: int,
           impl: str | None = None) -> jax.Array:
    """(u*v) mod B^L with traced L (close product)."""
    return mask_below(mul(u, v, out_width, impl=impl), L)


@partial(jax.jit, static_argnames=("out_width", "impl"))
def mul_jit(u, v, out_width: int, impl: str | None = None):
    return mul(u, v, out_width, impl=impl)


@partial(jax.jit, static_argnames=("out_width", "impl"))
def mul_batch_jit(u, v, out_width: int, impl: str | None = None):
    return mul_batch(u, v, out_width, impl=impl)


# ---------------------------------------------------------------------------
# fused division-step registry (kernels/fused.py)
#
# One Refine iteration of the shifted-inverse Newton loop is
#   PowDiff product + sign/magnitude select + w*x product + shift/add/
#   sub + floor correction
# and the paper's CUDA implementation fuses ALL of that into the same
# kernels that do the multiplications (which is why its cost model can
# count multiplications only).  These entry points are the JAX
# analogue: with impl="pallas_fused" each of them compiles to batched
# Pallas launches with the glue arithmetic executed in-kernel on the
# VMEM-resident tiles (fused_step: 2 launches, fused_correct /
# fused_barrett: 1 launch each); with any other impl they fall back to
# the reference composition (K.mul products + core.arith glue in XLA,
# ~15 full-width ops per step).
# ---------------------------------------------------------------------------

def fused_step(v, w, *, h, m, l, s, active, g: int, win: int,
               impl: str | None = None, name: str = "refine"):
    """One guarded Refine iteration on the full-width iterate.

    v, w: (W,) limb vectors (w is the current iterate, already guard-
    shifted); h/m/l/s traced int32 scalars, `active` a traced bool,
    `g` the static guard digit count, `win` the static window width of
    this iteration (win == W when not windowed).  Returns the updated
    full-width iterate (the -1 normalization shift and the
    active-instance select are folded in).  Batch with jax.vmap: the
    pallas_fused path routes the whole batch into 2 native launches,
    named after `name` (`fused.step_pallas`).
    """
    from . import fused
    impl = impl or default_impl()
    if impl == "pallas_fused":
        return fused.step_pallas(v, w, h=h, m=m, l=l, s=s, active=active,
                                 g=g, win=win, name=name)
    return fused.step_reference(v, w, h=h, m=m, l=l, s=s, active=active,
                                g=g, win=win, impl=impl)


def fused_correct(u, v, si, *, h, impl: str | None = None):
    """divmod finalization: q = floor(u * si / B^h), mm = v*q, then the
    delta in {-1,0,+1} compare-and-correct.  u, v, si: (W,) limbs, h a
    traced int32 scalar.  Returns (q, r) at width W; divides by zero as
    the documented total extension (q, r) = (0, u).  One batched Pallas
    launch under impl="pallas_fused"."""
    from . import fused
    impl = impl or default_impl()
    if impl == "pallas_fused":
        return fused.correct_pallas(u, v, si, h=h)
    return fused.correct_reference(u, v, si, h=h, impl=impl)


def fused_barrett(x, mu, v, *, h: int, impl: str | None = None):
    """Barrett reduction core: two truncated products + two conditional
    subtracts at STATIC shift h.  x, mu, v: (W,) limbs.  Returns r at
    width W (caller slices to the modulus width).  One batched Pallas
    launch under impl="pallas_fused"."""
    from . import fused
    impl = impl or default_impl()
    if impl == "pallas_fused":
        return fused.barrett_pallas(x, mu, v, h=h)
    return fused.barrett_reference(x, mu, v, h=h, impl=impl)
