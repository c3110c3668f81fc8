"""Pallas TPU kernel for classical multi-precision multiplication.

TPU-native adaptation of the paper's Fig. 2 block-scheduled quadratic
multiplication:

  CUDA (paper)                          TPU Pallas (here)
  ------------------------------------  --------------------------------
  one instance per CUDA block           one instance (block) per leading
                                        grid row (`mul_pallas_batched`)
  operands staged in shared memory      operand tiles in VMEM; Toeplitz
                                        tiles built in-kernel from the
                                        raw sub-digit block (BlockSpec)
  per-thread Q-element digit loops      (T x 2T) Toeplitz tiles on the MXU
  64-bit digits                         16-bit limbs split to 8-bit
                                        sub-digits; bf16 MXU tiles with
                                        f32 sums, int32 accumulation
  warp shuffles for carries             carry pre-resolution fused into
                                        the kernel epilogue; one short
                                        associative-scan fixup in XLA

The product is a convolution of base-2^8 sub-digit sequences.  It is
blocked into T-sized tiles; each (i, j) block pair contributes
u_i (1 x T) @ Toep(v_j) (T x 2T) to output diagonal d = i + j.  A
scalar-prefetched schedule walks the pairs grouped by diagonal so the
output tile stays resident in VMEM and is accumulated in int32 across
the pairs of its diagonal (grid revisiting).

Two generations of the kernel live here:

  * `mul_pallas` / `mulmod_pallas` -- single instance, batched by the
    generic `jax.vmap` rule.  Toeplitz tiles are pre-materialized on
    the host as a (nv, t, 2t) tensor (a ~2t-times blowup of the
    operand) and the full carry resolution (4 local passes + scan)
    runs in XLA on raw per-diagonal sums.
  * `mul_pallas_batched` -- the batch is a native leading grid axis
    (BLOCK_B instances per grid step), Toeplitz tiles are staged in
    VMEM inside the kernel by one strided lane rotate of the raw
    sub-digit block (no host-side blowup), and the last pair of each
    diagonal pre-resolves its tile's carries in the epilogue, so XLA
    only overlap-adds small (< 2^9) digits and finishes with a 2-pass
    + associative-scan fixup.  This is the paper's Fig. 2
    one-instance-per-block schedule; `impl="pallas_batched"` in
    kernels/ops.py.

Exactness: sub-digits < 2^8, tile products < 2^16 * T, a diagonal
accumulates at most min(nu, nv) tiles: max raw value
min(nu,nv) * T * 255^2 < 2^31 for operands up to 2^18 bits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bigint import MASK
from . import ops as K
from .ops import _to_u8digits, _resolve8, _pack8, BLOCK_T

_I = jnp.int32
_U = jnp.uint32


def _toeplitz_host(v8: jax.Array, nv: int, t: int) -> jax.Array:
    """(nv*t,) sub-digits -> (nv, t, 2t) Toeplitz tiles (XLA gather).

    Toep[j, c, s] = v8[j*t + s - c] when 0 <= s - c < t else 0.
    Built outside the kernel: a memory-bound gather that XLA fuses;
    the kernel consumes the tiles with pure MXU matmuls.
    """
    vg = jnp.concatenate([jnp.zeros((t,), _I), v8.astype(_I),
                          jnp.zeros((t,), _I)])
    j = jnp.arange(nv, dtype=_I)[:, None, None]
    c = jnp.arange(t, dtype=_I)[None, :, None]
    s = jnp.arange(2 * t, dtype=_I)[None, None, :]
    tile = jnp.take(vg, j * t + s - c + t, axis=0)
    return jnp.where((s - c >= 0) & (s - c < t), tile, 0)


def _pair_schedule_pruned(nu: int, nv: int,
                          d_keep: int | None = None) -> tuple[np.ndarray, ...]:
    """Static schedule: (i, j) block pairs with i+j < d_keep, sorted by
    diagonal d = i+j.

    Returns (i_idx, j_idx, d_idx, first_flag, last_flag) int32 arrays;
    first_flag marks the first pair of each diagonal (output tile must
    be zero-initialized on revisit-entry), last_flag the last (the
    batched kernel runs its carry pre-resolution epilogue there).
    """
    if d_keep is None:
        d_keep = nu + nv - 1
    pairs = [(i + j, i, j) for i in range(nu) for j in range(nv)
             if i + j < d_keep]
    pairs.sort()
    d_idx = np.array([p[0] for p in pairs], dtype=np.int32)
    i_idx = np.array([p[1] for p in pairs], dtype=np.int32)
    j_idx = np.array([p[2] for p in pairs], dtype=np.int32)
    bound = (d_idx[1:] != d_idx[:-1]).astype(np.int32)
    first = np.ones(len(pairs), dtype=np.int32)
    first[1:] = bound
    last = np.ones(len(pairs), dtype=np.int32)
    last[:-1] = bound
    return i_idx, j_idx, d_idx, first, last


def _pair_schedule(nu: int, nv: int) -> tuple[np.ndarray, ...]:
    """All (i, j) block pairs sorted by diagonal (no pruning, no last
    flags) -- the single-instance kernel's schedule."""
    return _pair_schedule_pruned(nu, nv)[:4]


def _mul_kernel(i_ref, j_ref, d_ref, f_ref, u_ref, t_ref, o_ref):
    """One grid step: accumulate u_i @ Toep(v_j) into diagonal tile.

    i/j/d/f_ref are the scalar-prefetched schedule (SMEM); u/t/o are the
    VMEM tiles selected by the BlockSpec index maps."""
    p = pl.program_id(0)
    tile = jnp.dot(u_ref[0, :][None, :], t_ref[0],
                   preferred_element_type=_I)     # (1, 2t) MXU product

    @pl.when(f_ref[p] == 1)
    def _init():
        o_ref[0, :] = tile[0, :]

    @pl.when(f_ref[p] == 0)
    def _acc():
        o_ref[0, :] = o_ref[0, :] + tile[0, :]


def _mul_pallas_raw(u8b: jax.Array, toep: jax.Array, nu: int, nv: int,
                    t: int) -> jax.Array:
    """Grid over diagonal-sorted block pairs -> (ndiag, 2t) raw sums."""
    i_idx, j_idx, d_idx, first = _pair_schedule(nu, nv)
    ndiag = nu + nv - 1
    return _call_pair_kernel(u8b, toep, i_idx, j_idx, d_idx, first,
                             ndiag, t)


def _call_pair_kernel(u8b, toep, i_idx, j_idx, d_idx, first, ndiag, t):
    """pallas_call over a static diagonal-sorted pair schedule.

    The schedule rides in SMEM via scalar prefetch; the BlockSpec index
    maps read it to pick the (u_i, Toep_j, diag_d) tiles per grid step.
    Consecutive steps of one diagonal revisit the same output block, so
    it stays resident in VMEM and accumulates in int32.
    """
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(len(i_idx),),
        in_specs=[
            pl.BlockSpec((1, t), lambda p, i, j, d, f: (i[p], 0)),
            pl.BlockSpec((1, t, 2 * t), lambda p, i, j, d, f: (j[p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 2 * t), lambda p, i, j, d, f: (d[p], 0)),
    )
    return pl.pallas_call(
        _mul_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ndiag, 2 * t), _I),
        interpret=K.interpret_mode(),
        name="bigmul",
    )(jnp.asarray(i_idx), jnp.asarray(j_idx), jnp.asarray(d_idx),
      jnp.asarray(first), u8b, toep)


def mul_pallas(u: jax.Array, v: jax.Array, out_width: int) -> jax.Array:
    """Exact u*v mod B^out_width via the Pallas kernel (single instance)."""
    t = BLOCK_T
    u8 = _to_u8digits(u.astype(_U))
    v8 = _to_u8digits(v.astype(_U))
    nu = max(-(-u8.shape[0] // t), 1)
    nv = max(-(-v8.shape[0] // t), 1)
    u8 = jnp.zeros((nu * t,), _U).at[: u8.shape[0]].set(u8)
    v8 = jnp.zeros((nv * t,), _U).at[: v8.shape[0]].set(v8)

    u8b = u8.reshape(nu, t).astype(_I)
    toep = _toeplitz_host(v8, nv, t)
    seg = _mul_pallas_raw(u8b, toep, nu, nv, t)              # (ndiag, 2t)

    ndiag = nu + nv - 1
    n8 = (ndiag + 1) * t
    raw = jnp.zeros((n8,), _I)
    raw = raw.at[: ndiag * t].add(seg[:, :t].reshape(-1))
    raw = raw.at[t:].add(seg[:, t:].reshape(-1))
    raw = raw.astype(_U)

    wo8 = 2 * out_width
    if n8 < wo8:
        raw = jnp.concatenate([raw, jnp.zeros((wo8 - n8,), _U)])
    else:
        raw = raw[:wo8]
    return _pack8(_resolve8(raw))


def mulmod_pallas(u: jax.Array, v: jax.Array, l_max: int,
                  out_width: int) -> jax.Array:
    """Close product: (u*v) mod B^l_max computed with only the low
    diagonals (the paper's MULTMOD work saving, Algorithm 2).

    l_max is a STATIC bound in base-2^16 limbs; only block diagonals
    that can touch sub-digits < 2*l_max are scheduled.
    """
    t = BLOCK_T
    u8 = _to_u8digits(u.astype(_U))
    v8 = _to_u8digits(v.astype(_U))
    nu = max(-(-u8.shape[0] // t), 1)
    nv = max(-(-v8.shape[0] // t), 1)
    # Exact pruning bound: pair (i, j) on diagonal d = i+j writes raw
    # sums only to sub-digit positions [d*t, (d+2)*t); the result keeps
    # positions < 2*l_max, and carries travel strictly upward, so a
    # pair contributes iff d*t < 2*l_max, i.e. d < ceil(2*l_max / t).
    # Tested at/around l_max multiples of BLOCK_T//2 in test_kernels.
    d_keep = -(-2 * l_max // t)
    nu_k = min(nu, d_keep)
    nv_k = min(nv, d_keep)
    u8 = jnp.zeros((nu_k * t,), _U).at[: min(u8.shape[0], nu_k * t)].set(
        u8[: nu_k * t])
    v8 = jnp.zeros((nv_k * t,), _U).at[: min(v8.shape[0], nv_k * t)].set(
        v8[: nv_k * t])

    u8b = u8.reshape(nu_k, t).astype(_I)
    toep = _toeplitz_host(v8, nv_k, t)

    i_idx, j_idx, d_idx, first, _ = _pair_schedule_pruned(nu_k, nv_k, d_keep)

    ndiag = int(d_idx.max()) + 1 if len(d_idx) else 1
    seg = _call_pair_kernel(u8b, toep, i_idx, j_idx, d_idx, first,
                            ndiag, t)

    n8 = (ndiag + 1) * t
    raw = jnp.zeros((n8,), _I)
    raw = raw.at[: ndiag * t].add(seg[:, :t].reshape(-1))
    raw = raw.at[t:].add(seg[:, t:].reshape(-1))
    raw = raw.astype(_U)

    wo8 = 2 * out_width
    if n8 < wo8:
        raw = jnp.concatenate([raw, jnp.zeros((wo8 - n8,), _U)])
    else:
        raw = raw[:wo8]
    limbs = _pack8(_resolve8(raw))
    idx = jnp.arange(out_width, dtype=_I)
    return jnp.where(idx < l_max, limbs, _U(0))


# ---------------------------------------------------------------------------
# natively batched kernel: batch as leading grid axis, in-kernel Toeplitz
# staging, fused carry pre-resolution
# ---------------------------------------------------------------------------

# Instances processed per grid step.  The VMEM working set per step is
# dominated by the (BLOCK_B, T, 2T) Toeplitz tiles: 16 * 128 * 256 *
# 4 B = 2 MiB, which with rotate temporaries stays well inside a TPU
# core's ~16 MiB VMEM.
MAX_BLOCK_B = 16


def pick_block_b(batch: int) -> int:
    """Batch-block size for `mul_pallas_batched`: the power of two
    <= MAX_BLOCK_B minimizing padded instance-steps ceil(batch/bb)*bb
    (ties go to the larger block -> fewer grid rows)."""
    best = 1
    bb = 2
    while bb <= MAX_BLOCK_B:
        if -(-batch // bb) * bb <= -(-batch // best) * best:
            best = bb
        bb *= 2
    return best


def _toep_tile(vblk: jax.Array) -> jax.Array:
    """(bb, t) sub-digit block -> (bb, t, 2t) bf16 Toeplitz tiles, in
    VMEM.

    tile[b, c, s] = vblk[b, s-c] when 0 <= s-c < t else 0: the
    zero-padded block broadcast to t rows, row c rotated by c in one
    strided lane rotate.  A rotate's wrap-around lands inside the
    length-t zero pad (pad[(s-c) mod 2t] with s-c outside [0, t) always
    hits the pad), so no boundary mask is needed.  The rotate works on
    32-bit lanes; sub-digits < 2^8 are then exact in bf16, the MXU
    operand type of `_tile_dot`.
    """
    bb, t = vblk.shape
    pad = jnp.concatenate([vblk, jnp.zeros_like(vblk)], axis=-1)
    mat = jnp.broadcast_to(pad[:, None, :], (bb, t, 2 * t))
    return pltpu.roll(mat, 0, 2, stride=1,
                      stride_axis=1).astype(jnp.bfloat16)


def _preresolve(e: jax.Array) -> jax.Array:
    """In-kernel carry pre-resolution of one widened diagonal tile.

    e: (bb, 3t) int32, raw sums < 2^31 in [:2t], zeros in the tail.
    Four local split passes shrink every entry to <= 2^8; carries past
    position 2t-1 walk into the widened tail (at most 4 positions), so
    nothing is dropped.  After overlap-add of the <=3 tiles covering a
    global position the sums are < 3*2^8 + 1, which the XLA fixup
    finishes with 2 passes + one associative scan (`_resolve8`).
    """
    w = e.shape[-1]
    idx = jax.lax.broadcasted_iota(_I, (1, w), 1)
    for _ in range(4):                      # carry magnitude /2^8 per pass
        d = e & 0xFF
        c = e >> 8
        up = jnp.where(idx >= 1, pltpu.roll(c, 1, c.ndim - 1), 0)
        e = d + up
    return e


def _tile_dot(u: jax.Array, toep: jax.Array) -> jax.Array:
    """(bb, t) sub-digit rows times their (bb, t, 2t) Toeplitz tiles ->
    (bb, 2t) int32 raw sums, one M = 1 row per instance on the MXU.

    The TPU's MXU takes no int32 operands, so the product runs in bf16
    with f32 accumulation, which is exact here: sub-digits are < 2^8
    (exact in bf16's 8-bit significand) and a T-term sum stays below
    T * 255^2 < 2^24 (exact in f32's 24-bit significand)."""
    bb, t = u.shape
    prod = jax.lax.dot_general(
        u.reshape(bb, 1, t).astype(jnp.bfloat16), toep,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)               # (bb, 1, 2t)
    return prod.reshape(bb, 2 * t).astype(_I)


def _mul_batched_kernel(i_ref, j_ref, d_ref, f_ref, l_ref,
                        u_ref, v_ref, o_ref):
    """One grid step: BLOCK_B instances of pair (i, j) on diagonal d.

    u_ref: (bb, t) sub-digit tiles of u block i; v_ref likewise for v
    block j; o_ref: (bb, 3t) widened diagonal-d accumulator.  The
    Toeplitz tiles never exist outside VMEM: they are rebuilt from
    v_ref by `_toep_tile` each step (pure VPU shuffles, overlapped with
    the MXU product of the previous step by the pipeline).
    """
    p = pl.program_id(1)
    t = u_ref.shape[-1]
    prod = _tile_dot(u_ref[...], _toep_tile(v_ref[...]))  # (bb, 2t)

    @pl.when(f_ref[p] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        o_ref[:, : 2 * t] = prod

    @pl.when(f_ref[p] == 0)
    def _acc():
        o_ref[:, : 2 * t] = o_ref[:, : 2 * t] + prod

    @pl.when(l_ref[p] == 1)
    def _epilogue():
        o_ref[...] = _preresolve(o_ref[...])


def mul_pallas_batched(u: jax.Array, v: jax.Array, out_width: int,
                       block_b: int | None = None) -> jax.Array:
    """Natively batched exact (u*v) mod B^out_width.

    u: (batch, Wu), v: (batch, Wv) base-2^16 limb batches ->
    (batch, out_width).  One instance group per leading grid row (the
    paper's one-instance-per-CUDA-block schedule), Toeplitz tiles
    staged in-kernel (no host-side (batch, nv, t, 2t) materialization),
    per-diagonal carries pre-resolved in the kernel epilogue.  Pairs
    whose diagonal cannot touch sub-digits < 2*out_width are pruned
    from the schedule structurally, like `_mul_blocked`.
    """
    if u.ndim != 2 or v.ndim != 2 or u.shape[0] != v.shape[0]:
        raise ValueError(f"expected (batch, W) operands with equal batch, "
                         f"got {u.shape} x {v.shape}")
    batch = u.shape[0]
    t = BLOCK_T
    wo8 = 2 * out_width
    u8 = _to_u8digits(u.astype(_U))[:, :wo8]   # sub-digits >= wo8 can't matter
    v8 = _to_u8digits(v.astype(_U))[:, :wo8]
    nu = max(-(-u8.shape[1] // t), 1)
    nv = max(-(-v8.shape[1] // t), 1)
    # diagonal d's first output sub-digit is d*t; pruning bound as in
    # mulmod_pallas (see its derivation)
    d_keep = -(-wo8 // t)
    nu_k = min(nu, d_keep)
    nv_k = min(nv, d_keep)
    u8 = u8[:, : nu_k * t]
    v8 = v8[:, : nv_k * t]
    u8 = jnp.pad(u8, ((0, 0), (0, nu_k * t - u8.shape[1])))
    v8 = jnp.pad(v8, ((0, 0), (0, nv_k * t - v8.shape[1])))

    bb = block_b or pick_block_b(batch)
    bp = -(-batch // bb) * bb
    if bp > batch:
        u8 = jnp.pad(u8, ((0, bp - batch), (0, 0)))
        v8 = jnp.pad(v8, ((0, bp - batch), (0, 0)))
    # (batch rows, tiles, bb, t): the blocks below squeeze the two
    # leading axes, so each block's last two dims equal the array's
    # whatever bb is (Mosaic's (8, 128) block rule)
    nr = bp // bb
    u8b = u8.reshape(nr, bb, nu_k, t).transpose(0, 2, 1, 3).astype(_I)
    v8b = v8.reshape(nr, bb, nv_k, t).transpose(0, 2, 1, 3).astype(_I)

    i_idx, j_idx, d_idx, first, last = _pair_schedule_pruned(
        nu_k, nv_k, d_keep)
    ndiag = min(nu_k + nv_k - 1, d_keep)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nr, len(i_idx)),
        in_specs=[
            pl.BlockSpec((None, None, bb, t),
                         lambda b, p, i, j, d, f, l: (b, i[p], 0, 0)),
            pl.BlockSpec((None, None, bb, t),
                         lambda b, p, i, j, d, f, l: (b, j[p], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, None, bb, 3 * t),
            lambda b, p, i, j, d, f, l: (b, d[p], 0, 0)),
    )
    seg = pl.pallas_call(
        _mul_batched_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nr, ndiag, bb, 3 * t), _I),
        interpret=K.interpret_mode(),
        name="bigmul_batched",
    )(jnp.asarray(i_idx), jnp.asarray(j_idx), jnp.asarray(d_idx),
      jnp.asarray(first), jnp.asarray(last), u8b, v8b)
    seg = seg.transpose(0, 2, 1, 3).reshape(bp, ndiag, 3 * t)

    # overlap-add of the pre-resolved tiles: global position g receives
    # the [0,t) lanes of tile g//t, the [t,2t) lanes of tile g//t - 1
    # and the tail lanes of tile g//t - 2 -- each entry <= 2^8, so sums
    # stay < 2^10 and the fixup needs only 2 local passes + one scan.
    n8 = (ndiag + 2) * t
    raw = jnp.zeros((bp, n8), _I)
    raw = raw.at[:, : ndiag * t].add(seg[:, :, :t].reshape(bp, -1))
    raw = raw.at[:, t: (ndiag + 1) * t].add(
        seg[:, :, t: 2 * t].reshape(bp, -1))
    raw = raw.at[:, 2 * t:].add(seg[:, :, 2 * t:].reshape(bp, -1))
    raw = raw.astype(_U)

    if n8 < wo8:
        raw = jnp.pad(raw, ((0, 0), (0, wo8 - n8)))
    else:
        raw = raw[:, :wo8]
    return _pack8(_resolve8(raw, passes=2))[:batch]
