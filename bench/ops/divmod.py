"""Exact division u = q v + r, 0 <= r < v, served by
`BigintDivisionService` through the frontend's "divmod" op.

Operands follow the paper's evaluation distribution (Table 1; the
generator of `benchmarks/table1_div.make_dataset`): prec(u) = M - 2
limbs and prec(v) uniform in [2, M/2] limbs, which runs Refine to its
full trip count.  The divisor precisions are dealt as one seeded
permutation of every value in [2, M/2], so that every seed sends the
same sizes in another order.  The reference is Python's `divmod`.
"""

from __future__ import annotations

from roofline import divmod_bytes, divmod_ops, refine_iters

OP = "divmod"
CHECK_ROWS = None               # every answered row is compared
B = 1 << 16


def build_service(cfg: dict):
    from repro.serving.bigint_service import BigintDivisionService
    return BigintDivisionService(**cfg["service"]["kwargs"])


def make_keys(rng, cfg: dict, n: int) -> list:
    if n:
        raise ValueError("divmod takes no modulus; set keys to 0")
    return []


class Rows:
    def __init__(self, cfg: dict, rng):
        self.m = cfg["m_limbs"]
        self.rng = rng
        self.v_prec = list(range(2, self.m // 2 + 1))
        rng.shuffle(self.v_prec)
        self._i = 0

    def take(self, n: int, v=None) -> tuple:
        lo, hi = B ** (self.m - 3), B ** (self.m - 2)
        us, vs = [], []
        for _ in range(n):
            kv = self.v_prec[self._i % len(self.v_prec)]
            self._i += 1
            us.append(self.rng.randrange(lo, hi))
            vs.append(self.rng.randrange(B ** (kv - 1), B ** kv))
        return us, vs


def call(service, cols, v=None):
    """The service endpoint the frontend drives, called directly."""
    return service.divide(*cols)


def result_rows(result) -> list:
    """Per-row answers of one request's result, in row order."""
    qs, rs = result
    return list(zip(qs, rs))


def reference_row(cols, v, i):
    return divmod(cols[0][i], cols[1][i])


def ops_per_row(cfg: dict) -> float:
    return divmod_ops(cfg["m_limbs"])


def bytes_per_row(cfg: dict) -> int:
    return divmod_bytes(cfg["m_limbs"])


def model_launches_per_call(cfg: dict) -> int:
    """2 launches per Refine iteration and 1 finalization (the fused
    path's contract)."""
    return 2 * refine_iters(cfg["m_limbs"]) + 1


def warm_keys(service, stream, cfg: dict) -> None:
    """No per-key state to warm."""


class Control:
    """The reference in the program's place with one guarantee broken:
    the quotient is the shifted-inverse estimate floor(u w / B^h),
    w = floor(B^h / v), h = prec(u), without the final +-1 correction
    the exact division needs (the paper's Theorem 2 step)."""

    def __init__(self, service):
        self._service = service             # batcher, validate, widths

    def __getattr__(self, name):
        return getattr(self._service, name)

    def divide(self, us, vs, *, impl=None):
        qs, rs = [], []
        for u, v in zip(us, vs):
            h = 16 * ((u.bit_length() + 15) // 16)
            q = (u * ((1 << h) // v)) >> h
            qs.append(q)
            rs.append(u - q * v)
        return qs, rs
