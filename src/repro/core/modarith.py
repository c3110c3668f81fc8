"""Modular arithmetic on the cached whole shifted inverse (Barrett).

The paper's `shinv_h(v) = floor(B^h / v)` is exactly a Barrett constant:
computed once by Newton iteration (shinv.py), every subsequent reduction
mod `v` costs two truncated multiplications plus at most two conditional
subtracts -- no further division.  This module packages that observation
as a subsystem:

  barrett_precompute(v) -> BarrettContext   one shinv, cached
  barrett_reduce(ctx, x)                    x mod v, 2 muls
  modmul(ctx, a, b)                         (a*b) mod v, 3 muls
  modexp(ctx, a, e)                         a^e mod v, fixed-window ladder

Amortization is the whole point: modexp over an n-bit exponent performs
~1.25 n modular reductions against ONE shinv, where the naive route
(divmod per step) re-runs the 5-7-multiplication Newton refinement every
time.  See benchmarks/modexp.py for the measured crossover.

JAX adaptation notes (mirroring shinv.py):

  * The textbook Barrett constant uses h = 2k + guard with k = prec(v),
    shrinking the constant for small moduli.  Under tracing every
    multiplication already executes at a static width, so a data-
    dependent h buys nothing; we fix h = 2 m + guard at the *storage*
    width m of the modulus (its worst case).  This also widens the
    valid domain of `barrett_reduce` from x < B^(2 prec(v)+guard) to
    every x < B^(2m) -- any double-width value reduces in one pass.
    `ctx.k = prec(v)` is kept as a traced diagnostic (cost accounting,
    tests).
  * Quotient-estimate error: with mu = floor(B^h/v) + lambda,
    lambda in {0,1} (Theorem 2) and any x < B^h,
        qhat = floor(x*mu / B^h)  in  {q-1, q, q+1},
    so correction is one conditional add-back plus one conditional
    subtract -- branch-free via `where`, SIMD-uniform across a batch.
  * `modexp` is a fixed-window ladder with a constant trip count
    (ceil(bits(e)/w) windows, each w squarings + 1 table multiply), the
    exponent a limb vector; per-instance variation is handled by the
    table select, so it traces at static shape and vmaps cleanly.

`impl` selects the kernel path ("scan" | "blocked" | "pallas" |
"pallas_batched" | "pallas_fused"), `windowed` the size-bucketed
Newton refinement -- both threaded through exactly like
`shinv.divmod_batch`.  With "pallas_batched" `K.mul` is batch-aware:
the vmapped per-row-context paths (`reduce_rows` / `modmul_rows` /
`modexp_rows`, which the serving layer runs, each row's context
gathered from a `ContextSlab`) and `reduce_shared` execute each
truncated multiplication as one natively batched kernel launch
across the whole request batch.  With "pallas_fused"
(the TPU default) the whole `barrett_reduce` core -- both truncated
products AND the conditional subtracts -- is ONE batched launch
(`K.fused_barrett`, kernels/fused.py); that single-launch contract
holds at every modulus size, because past ~2^13-bit working widths
the fused kernel switches to its grid-scheduled generation (pair axis
on the Pallas grid, bounded per-step VMEM) instead of unrolling.

Module contract: `barrett_reduce` requires x < B^(2m) (ValueError
above), is exact for any modulus v >= 1, and a context is only valid
for the modulus it was precomputed from; `modexp`'s trip count is
data-independent (constant-time-shaped).  v == 0 is the caller's to
reject -- `barrett_precompute` documents v >= 1 (the serving layer
raises before building a context).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .bigint import LOG_BASE, DTYPE, one_hot_pow
from . import arith as A
from .shinv import PAD, shinv_fixed
from repro.kernels import ops as K

_U = jnp.uint32
_I = jnp.int32

MU_GUARD = 2    # guard digits above 2m in h (keeps qhat error in {-1,0,+1})


def barrett_h(m: int) -> int:
    """Static shift h of the cached inverse for an m-limb modulus."""
    return 2 * m + MU_GUARD


def barrett_width(m: int) -> int:
    """Working width of the reduction: holds B^h plus headroom."""
    return barrett_h(m) + PAD


class BarrettContext(NamedTuple):
    """Device-resident per-modulus state.  All fields are arrays, so a
    context vmaps (per-instance moduli) and jits (cached reuse) as-is."""
    v: jax.Array     # (m,) modulus limbs
    mu: jax.Array    # (barrett_width(m),) shinv_h(v) + lambda, lambda in {0,1}
    k: jax.Array     # int32 prec(v) -- diagnostic, not on the hot path

    @property
    def m(self) -> int:
        return self.v.shape[0]


def _pad_to(u: jax.Array, width: int) -> jax.Array:
    return jnp.zeros((width,), _U).at[: u.shape[0]].set(u.astype(_U))


def barrett_precompute(v: jax.Array, *, impl: str | None = None,
                       windowed: bool = True) -> BarrettContext:
    """One Newton-iterated shinv at h = 2m + guard; everything after
    this is division-free.  v: (m,) limbs, v >= 1."""
    m = v.shape[0]
    W = barrett_width(m)
    h = barrett_h(m)
    # h - k <= h - 1 bounds the refinement length (shinv.py `need`)
    iters_max = math.ceil(math.log2(max(h - 1, 2))) + 2
    mu = shinv_fixed(_pad_to(v, W), h, iters_max=iters_max, impl=impl,
                     windowed=windowed)
    return BarrettContext(v=v.astype(DTYPE), mu=mu, k=A.prec(v))


def barrett_reduce(ctx: BarrettContext, x: jax.Array,
                   *, impl: str | None = None) -> jax.Array:
    """x mod v for any x < B^(2m), as (m,) limbs.  Two truncated
    multiplications; exactness is guaranteed by the qhat error bound
    (asserted against divmod_fixed in tests).

    The reduction core (qhat = floor(x*mu / B^h), q*v, the conditional
    add-back/subtract) runs through `K.fused_barrett`: ONE batched
    Pallas launch under impl="pallas_fused" (h is static, so the shift
    compiles into the kernel), the reference composition elsewhere.
    """
    m = ctx.m
    if x.shape[0] > 2 * m:
        raise ValueError(f"x has {x.shape[0]} limbs; reduce handles <= {2*m}")
    W = barrett_width(m)
    h = barrett_h(m)
    xw = _pad_to(x, W)
    vw = _pad_to(ctx.v, W)
    # x*mu < B^(2m + h + 1) <= B^(2W), so the first product's 2W-limb
    # truncation cuts nothing needed; q*v <= x + v < B^W fits the
    # second; qhat in {q-1, q, q+1} makes the correction two
    # conditional subtracts.
    r = K.fused_barrett(xw, ctx.mu, vw, h=h, impl=impl)
    return r[:m]


def modmul(ctx: BarrettContext, a: jax.Array, b: jax.Array,
           *, impl: str | None = None) -> jax.Array:
    """(a * b) mod v for a, b < B^m (not necessarily reduced)."""
    m = ctx.m
    t = K.mul(a.astype(_U), b.astype(_U), 2 * m, impl=impl)
    return barrett_reduce(ctx, t, impl=impl)


def modexp(ctx: BarrettContext, a: jax.Array, e: jax.Array,
           *, window_bits: int = 4, impl: str | None = None) -> jax.Array:
    """a^e mod v by a fixed-window ladder with constant trip count.

    a: (m,) limbs, e: (e_limbs,) limbs.  Every instance executes the
    same ceil(bits/w) windows of w squarings + 1 table multiply; leading
    zero windows multiply by table[0] = 1 mod v, so the schedule is
    data-independent (vmap/SIMD-uniform, constant-time-shaped).
    """
    if LOG_BASE % window_bits != 0:
        raise ValueError(f"window_bits must divide {LOG_BASE}")
    m = ctx.m
    a_r = barrett_reduce(ctx, _pad_to(a, m), impl=impl)
    one_r = barrett_reduce(ctx, one_hot_pow(0, m), impl=impl)   # 1 mod v

    # table[i] = a^i mod v; built by scan so modmul traces once here
    def tb(prev, _):
        return modmul(ctx, prev, a_r, impl=impl), prev
    _, table = jax.lax.scan(tb, one_r, None, length=1 << window_bits)

    n_win = e.shape[0] * LOG_BASE // window_bits
    wmask = _U((1 << window_bits) - 1)

    def body(r, i):
        start = (_I(n_win - 1) - i) * _I(window_bits)   # MSB-first
        limb = start // _I(LOG_BASE)
        off = (start % _I(LOG_BASE)).astype(_U)
        d = (A.take_limb(e.astype(_U), limb) >> off) & wmask

        def sq(rr, _):
            return modmul(ctx, rr, rr, impl=impl), None
        r, _ = jax.lax.scan(sq, r, None, length=window_bits)
        r = modmul(ctx, r, jnp.take(table, d.astype(_I), axis=0), impl=impl)
        return r, None

    r, _ = jax.lax.scan(body, one_r, jnp.arange(n_win, dtype=_I))
    return r


# ---------------------------------------------------------------------------
# batched entry points (impl/windowed dispatch threaded like divmod_batch)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("impl", "windowed"))
def reduce_batch(x: jax.Array, v: jax.Array, impl: str | None = None,
                 windowed: bool = True):
    """Per-instance moduli: x (batch, <=2m), v (batch, m)."""
    def one(xi, vi):
        ctx = barrett_precompute(vi, impl=impl, windowed=windowed)
        return barrett_reduce(ctx, xi, impl=impl)
    return jax.vmap(one)(x, v)


@partial(jax.jit, static_argnames=("impl", "windowed"))
def modmul_batch(a: jax.Array, b: jax.Array, v: jax.Array,
                 impl: str | None = None, windowed: bool = True):
    def one(ai, bi_, vi):
        ctx = barrett_precompute(vi, impl=impl, windowed=windowed)
        return modmul(ctx, ai, bi_, impl=impl)
    return jax.vmap(one)(a, b, v)


@partial(jax.jit, static_argnames=("impl", "windowed", "window_bits"))
def modexp_batch(a: jax.Array, e: jax.Array, v: jax.Array,
                 impl: str | None = None, windowed: bool = True,
                 window_bits: int = 4):
    """Per-instance moduli: precompute folded in (no amortization)."""
    def one(ai, ei, vi):
        ctx = barrett_precompute(vi, impl=impl, windowed=windowed)
        return modexp(ctx, ai, ei, window_bits=window_bits, impl=impl)
    return jax.vmap(one)(a, e, v)


# Per-row contexts: a slab of cached contexts on the device, one per
# slot; each row of a batch gathers its own by slot index inside the
# program.  The serving layer's hot path (serving/modexp_service.py):
# one key is the case where every row names the same slot.

class ContextSlab(NamedTuple):
    """Barrett contexts of up to C moduli, one per slot, stacked like a
    batched `BarrettContext`."""
    v: jax.Array     # (C, m)
    mu: jax.Array    # (C, barrett_width(m))
    k: jax.Array     # (C,) int32


def empty_slab(slots: int, m: int) -> ContextSlab:
    # host zeros, copied in: no device program to compile
    w = barrett_width(m)
    return ContextSlab(v=jnp.asarray(np.zeros((slots, m), DTYPE)),
                       mu=jnp.asarray(np.zeros((slots, w), _U)),
                       k=jnp.asarray(np.zeros((slots,), _I)))


def precompute_into(slab: ContextSlab, v: jax.Array, slots: jax.Array,
                    *, impl: str | None = None,
                    windowed: bool = True) -> ContextSlab:
    """One batched precompute of the moduli v (n, m), each written into
    its slot (slots: (n,) int32).  Rows that name one slot must carry
    one modulus (padding repeats a row)."""
    ctx = jax.vmap(partial(barrett_precompute, impl=impl,
                           windowed=windowed))(v)
    return ContextSlab(*(s.at[slots].set(c) for s, c in zip(slab, ctx)))


def rows_context(slab: ContextSlab, slots: jax.Array) -> BarrettContext:
    """The batched context of rows whose moduli sit in `slots`."""
    return BarrettContext(*(s[slots] for s in slab))


def reduce_rows(slab: ContextSlab, slots: jax.Array, x: jax.Array,
                impl: str | None = None):
    """[x_i mod v_i], row i's modulus in slot slots[i]."""
    return jax.vmap(lambda c, xi: barrett_reduce(c, xi, impl=impl))(
        rows_context(slab, slots), x)


def modmul_rows(slab: ContextSlab, slots: jax.Array, a: jax.Array,
                b: jax.Array, impl: str | None = None):
    return jax.vmap(lambda c, ai, bi_: modmul(c, ai, bi_, impl=impl))(
        rows_context(slab, slots), a, b)


def modexp_rows(slab: ContextSlab, slots: jax.Array, a: jax.Array,
                e: jax.Array, impl: str | None = None,
                window_bits: int = 4):
    return jax.vmap(lambda c, ai, ei: modexp(c, ai, ei, impl=impl,
                                             window_bits=window_bits))(
        rows_context(slab, slots), a, e)


# One context broadcast across the batch (kernel tests and benchmarks).

def reduce_shared(ctx: BarrettContext, x: jax.Array,
                  impl: str | None = None):
    return jax.vmap(lambda xi: barrett_reduce(ctx, xi, impl=impl))(x)


@partial(jax.jit, static_argnames=("impl",))
def reduce_shared_batch(ctx, x, impl: str | None = None):
    return reduce_shared(ctx, x, impl=impl)
