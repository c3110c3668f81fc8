"""Multi-precision integer representation for JAX.

A big integer is a fixed-width little-endian vector of base-2^16 digits
("limbs") stored in uint32.  This is the TPU-native adaptation of the
paper's 64-bit-digit CUDA representation:

  * TPU VPUs operate natively on 32-bit lanes; 64-bit integer multiply
    is not hardware-supported, so the paper's `uint64` digits do not
    transfer.  With 16-bit digits, a digit product fits in uint32
    exactly, and up to 2^15 partial products can be accumulated in a
    uint32 before carry resolution (enough for 2^18-bit operands, the
    paper's largest size: 2^18 bits = 16384 base-2^16 limbs).
  * Carry/borrow propagation maps onto `lax.associative_scan` -- the
    same scan-based formulation as the paper's block-level `scanBlk`.
  * The classical multiplication maps onto block-Toeplitz integer
    matmuls (see kernels/), replacing CUDA per-thread digit loops with
    MXU/VPU-friendly dense products.

Host-side conversion helpers here are NumPy-only (not traced): a batch
of Python ints becomes one little-endian byte buffer (`int.to_bytes`)
viewed as uint16 limbs, and back through `int.from_bytes` per row, so
conversion is linear in the operand width.
"""

from __future__ import annotations

import operator

import numpy as np
import jax.numpy as jnp

LOG_BASE = 16                  # bits per digit
BASE = 1 << LOG_BASE           # digit base B = 65536
MASK = BASE - 1
DTYPE = jnp.uint32             # storage dtype (value of each limb < B)


def width_for_bits(bits: int) -> int:
    """Number of limbs for an integer precision in bits."""
    return -(-bits // LOG_BASE)


def from_int(x: int, m: int) -> np.ndarray:
    """Python int -> little-endian limb vector of length m (host)."""
    return batch_from_ints([x], m)[0]


def to_int(limbs) -> int:
    """Limb vector -> Python int (host)."""
    return batch_to_ints(np.asarray(limbs).reshape(1, -1))[0]


def _limb_bytes(x, m: int) -> bytes:
    x = operator.index(x)
    if x < 0:
        raise ValueError("unsigned representation only")
    try:
        return x.to_bytes(2 * m, "little")
    except OverflowError:
        raise OverflowError("value does not fit in m limbs") from None


def batch_from_ints(xs, m: int) -> np.ndarray:
    """Python ints -> (n, m) uint32 limb array (host), one byte pass per row."""
    xs = list(xs)
    buf = b"".join(_limb_bytes(x, m) for x in xs)
    return (np.frombuffer(buf, dtype="<u2").reshape(len(xs), m)
            .astype(np.uint32))


def batch_to_ints(arr) -> list[int]:
    """(n, m) limb array -> n Python ints (host), one byte pass per row.

    Every limb the kernels produce is < B.  A hand-built row with larger
    limbs still reads as the exact sum of d_i * B^i, one 16-bit plane at
    a time."""
    a = np.asarray(arr)
    if a.dtype.kind == "i" and a.size and a.min() < 0:
        raise ValueError("unsigned representation only")
    if not a.size or a.max() <= MASK:
        return [int.from_bytes(row.tobytes(), "little")
                for row in a.astype("<u2")]
    a = a.astype(np.uint64)
    out = [0] * len(a)
    shift = 0
    while a.any():
        out = [x + (p << shift)
               for x, p in zip(out, batch_to_ints(a & MASK))]
        a >>= LOG_BASE
        shift += LOG_BASE
    return out


def random_ints(rng: np.random.Generator, n: int, digits: int,
                exact_prec: bool = False) -> list[int]:
    """n random ints with <= `digits` base-B digits (>= if exact_prec)."""
    out = []
    for _ in range(n):
        d = digits if exact_prec else int(rng.integers(1, digits + 1))
        lo = BASE ** (d - 1) if exact_prec else 0
        hi = BASE ** d
        out.append(int(rng.integers(lo, hi, dtype=np.uint64)) if hi <= 2**64
                   else _rand_big(rng, lo, hi))
    return out


def _rand_big(rng: np.random.Generator, lo: int, hi: int) -> int:
    span = hi - lo
    nb = span.bit_length()
    while True:
        x = 0
        for _ in range(-(-nb // 32)):
            x = (x << 32) | int(rng.integers(0, 1 << 32, dtype=np.uint64))
        x &= (1 << nb) - 1
        if x < span:
            return lo + x


def zeros(m: int):
    return jnp.zeros((m,), dtype=DTYPE)


def one_hot_pow(p, m: int):
    """B^p as an m-limb vector (0 if p >= m), p may be traced."""
    idx = jnp.arange(m, dtype=jnp.int32)
    return jnp.where(idx == p, jnp.uint32(1), jnp.uint32(0))
