"""Modular exponentiation a^e mod v, served by `ModArithService`
through the frontend's "modexp" op (one cached Barrett context per
modulus, a fixed-window ladder).

Moduli are random odd integers of the configuration's full width with
the top bit set; bases are uniform below the modulus and exponents
uniform over the exponent width.  The reference is Python's `pow`,
which costs some tens of milliseconds a row at 2048 bits, so a seeded
sample of the answered rows is compared.
"""

from __future__ import annotations

from roofline import modexp_bytes, modexp_ladder, modexp_ops

OP = "modexp"
CHECK_ROWS = 600                # seeded sample of the answered rows
LIMB_BITS = 16


def build_service(cfg: dict):
    from repro.serving.modexp_service import ModArithService
    return ModArithService(**cfg["service"]["kwargs"])


def make_keys(rng, cfg: dict, n: int) -> list:
    bits = LIMB_BITS * cfg["m_limbs"]
    return [rng.getrandbits(bits) | (1 << (bits - 1)) | 1
            for _ in range(max(n, 1))]


class Rows:
    def __init__(self, cfg: dict, rng):
        self.e_bits = LIMB_BITS * cfg["e_limbs"]
        self.rng = rng

    def take(self, n: int, v) -> tuple:
        return ([self.rng.randrange(v) for _ in range(n)],
                [self.rng.getrandbits(self.e_bits) for _ in range(n)])


def call(service, cols, v):
    return service.modexp(cols[0], cols[1], v)


def result_rows(result) -> list:
    return list(result)


def reference_row(cols, v, i):
    return pow(cols[0][i], cols[1][i], v)


def ops_per_row(cfg: dict) -> float:
    return modexp_ops(cfg["m_limbs"], cfg["e_limbs"], cfg["window_bits"])


def bytes_per_row(cfg: dict) -> int:
    return modexp_bytes(cfg["m_limbs"], cfg["e_limbs"])


def model_launches_per_call(cfg: dict) -> int:
    """2 launches per ladder modmul (product, fused Barrett) and 1 for
    each of the two initial reductions."""
    lad = modexp_ladder(LIMB_BITS * cfg["e_limbs"], cfg["window_bits"])
    return 2 * lad["modmuls"] + 2


def warm_keys(service, stream, cfg: dict) -> None:
    """Fill the context cache as the traffic would: walk the stream's
    keys until the cache holds as many contexts as it can keep or as
    the mix has keys (the window's stream continues from there)."""
    want = min(cfg["service"]["kwargs"]["max_cached_moduli"],
               len(stream.keys))
    while service.stats()["ctx_cache"]["size"] < want:
        service.context(stream.next_key())


def _barrett(x: int, v: int, mu: int, k: int, subtracts: int) -> int:
    q = ((x >> (k - 1)) * mu) >> (k + 1)
    r = x - q * v
    for _ in range(subtracts):
        if r >= v:
            r -= v
    return r


def control_modexp(a: int, e: int, v: int, e_bits: int,
                   window_bits: int) -> int:
    """The ladder in Python with Barrett reductions, the last of which
    skips its conditional subtracts: the answer is congruent to a^e but
    not always reduced below v."""
    k = v.bit_length()
    mu = (1 << (2 * k)) // v

    def red(x, subtracts=2):
        return _barrett(x, v, mu, k, subtracts)

    table = [red(1)]
    ar = red(a)
    for _ in range((1 << window_bits) - 1):
        table.append(red(table[-1] * ar))
    r = table[0]
    mask = (1 << window_bits) - 1
    n_win = e_bits // window_bits
    for i in reversed(range(n_win)):
        for _ in range(window_bits):
            r = red(r * r)
        r = red(r * table[(e >> (i * window_bits)) & mask],
                subtracts=0 if i == 0 else 2)
    return r


class Control:
    """The reference in the program's place, with the guarantee that
    every residue lies below the modulus broken (see `control_modexp`)."""

    def __init__(self, service):
        self._service = service             # batcher, validate, widths
        self._keys = set()

    def __getattr__(self, name):
        return getattr(self._service, name)

    def context(self, v):
        self._keys.add(v)

    def stats(self) -> dict:
        return {"ctx_cache": {"size": len(self._keys), "hits": 0,
                              "misses": 0}}

    def modexp(self, a, e, v, *, impl=None):
        svc = self._service
        return [control_modexp(x, y, v, LIMB_BITS * svc.e_limbs,
                               svc.window_bits) for x, y in zip(a, e)]
