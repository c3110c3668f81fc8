"""Work and bytes of one completed operation, from the paper's cost model.

A copy of the multiplication arithmetic of `repro.obs.costmodel`
(`refine_iters`, `refine_window`, `refine_mul_work`, `modexp_ladder`),
kept here so that a change to the program cannot change the yardstick.
The copy imports nothing of the program; a test holds it equal to
today's cost model.

Work is counted in 8-bit sub-digit multiply-accumulates, two operations
each, and held against the chip's int8 peak: a 16-bit limb product is
four sub-digit products, and the int8 MXU rate is the densest bit-product
rate the chip has, so no formulation of the same products can read above
it.  Bytes are the operand and result rows of the call as they are
stored, 4 bytes per 16-bit limb.  Both are fixed per configuration: they
never read the program's iteration or launch counts.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

LIMB_BITS = 16
SUBDIGITS_PER_LIMB = 2          # 8-bit sub-digits of a 16-bit limb
OPS_PER_MAC = 2                 # a multiply-accumulate is two operations
STORED_BYTES_PER_LIMB = 4       # limbs travel as uint32

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def refine_iters(m_limbs: int) -> int:
    """Static Refine trip count ceil(log2 M) + 2 (Algorithm 1, line 19)."""
    return math.ceil(math.log2(max(m_limbs, 2))) + 2


def refine_window(i: int, width: int) -> int:
    """Operand window, in limbs, of Refine iteration i at `width`."""
    return min(max(32, 2 ** (i + 1) + 16), width)


def refine_mul_work(m_limbs: int) -> float:
    """Refine multiplication work in full M x M products: two products
    per iteration, each at its iteration's window."""
    return sum(2.0 * (refine_window(i, m_limbs) / m_limbs) ** 2
               for i in range(refine_iters(m_limbs)))


def modexp_ladder(e_bits: int, window_bits: int) -> dict:
    """Modular multiplications of the fixed-window ladder: a square per
    exponent bit, one table multiply per window, 2^w table entries."""
    if e_bits % window_bits:
        raise ValueError("window_bits must divide the exponent width")
    n_win = e_bits // window_bits
    return {"n_windows": n_win, "squarings": e_bits,
            "table_muls": 1 << window_bits, "window_muls": n_win,
            "modmuls": e_bits + (1 << window_bits) + n_win}


def limb_products_to_ops(n: float) -> float:
    """16-bit limb products -> int8 operations (2 per sub-digit MAC)."""
    return n * SUBDIGITS_PER_LIMB ** 2 * OPS_PER_MAC


def divmod_ops(m_limbs: int) -> float:
    """One division at M limbs: the windowed Refine products plus the
    two finalization products (u * shinv and q * v), each M x M."""
    full = refine_mul_work(m_limbs) + 2.0
    return limb_products_to_ops(full * m_limbs * m_limbs)


def divmod_bytes(m_limbs: int) -> int:
    """u and v in, q and r out."""
    return 4 * m_limbs * STORED_BYTES_PER_LIMB


def barrett_limb_products(m_limbs: int) -> int:
    """Two truncated M x M products (x * mu keeps the high half, q * v
    the low half): m (m + 1) / 2 limb products each."""
    return 2 * (m_limbs * (m_limbs + 1) // 2)


def modexp_ops(m_limbs: int, e_limbs: int, window_bits: int) -> float:
    """One modexp: each ladder modmul is one full M x M product plus a
    Barrett reduction; two more reductions bring a and 1 into range."""
    modmuls = modexp_ladder(e_limbs * LIMB_BITS, window_bits)["modmuls"]
    per_modmul = m_limbs * m_limbs + barrett_limb_products(m_limbs)
    return limb_products_to_ops(modmuls * per_modmul
                                + 2 * barrett_limb_products(m_limbs))


def modexp_bytes(m_limbs: int, e_limbs: int) -> int:
    """a and e in, the residue out (the modulus context is cached)."""
    return (2 * m_limbs + e_limbs) * STORED_BYTES_PER_LIMB


def load_peaks(device_kind: str) -> dict:
    """Peaks of `device_kind`; a kind that is not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def roofline(ops: float, nbytes: float, seconds: float,
             peaks: dict) -> tuple[float, str]:
    """(share of the roofline in %, the bound that binds): the least
    time the chip could take for `ops` and `nbytes` over `seconds`."""
    t_ops = ops / peaks["int8_ops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "int8_ops" if t_ops >= t_bytes else "hbm_bytes"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
