"""Serving layer: ModArithService context cache + shared batching."""

import random

import pytest

from repro.core import bigint as bi
from repro.serving import batching as BT
from repro.serving.modexp_service import ModArithService

B = bi.BASE


# ---------------------------------------------------------------------------
# batching machinery (shared with BigintDivisionService)
# ---------------------------------------------------------------------------

def test_batcher_plan():
    bt = BT.Batcher((4, 16))
    assert bt.bucket_for(1) == 4
    assert bt.bucket_for(5) == 16
    assert bt.bucket_for(99) == 16          # oversized -> largest
    assert bt.plan(3) == [(0, 3, 4)]
    assert bt.plan(16) == [(0, 16, 16)]
    # oversized: largest-bucket chunks, fitted tail
    assert bt.plan(35) == [(0, 16, 16), (16, 32, 16), (32, 35, 4)]


def test_pad_ints():
    assert BT.pad_ints([5, 6], 4, 1) == [5, 6, 1, 1]
    assert BT.pad_ints([5], 1, 0) == [5]


# ---------------------------------------------------------------------------
# ModArithService
# ---------------------------------------------------------------------------

def test_service_endpoints_exact():
    rnd = random.Random(5)
    m = 8
    svc = ModArithService(m_limbs=m, e_limbs=2, batch_buckets=(4,))
    v = rnd.randint(2, B ** m - 1)
    xs = [rnd.randint(0, B ** (2 * m) - 1) for _ in range(10)]
    assert svc.reduce(xs, v) == [x % v for x in xs]   # splits 10 > 4
    a = [rnd.randint(0, B ** m - 1) for _ in range(3)]
    b = [rnd.randint(0, B ** m - 1) for _ in range(3)]
    assert svc.modmul(a, b, v) == [(x * y) % v for x, y in zip(a, b)]
    e = [rnd.randint(0, B ** 2 - 1) for _ in range(3)]
    assert svc.modexp(a, e, v) == [pow(x, y, v) for x, y in zip(a, e)]


def test_service_context_cache_and_lru():
    rnd = random.Random(6)
    m = 4
    svc = ModArithService(m_limbs=m, e_limbs=1, batch_buckets=(2,),
                          max_cached_moduli=2)
    vs = [rnd.randint(2, B ** m - 1) for _ in range(3)]
    for v in vs:
        svc.reduce([rnd.randint(0, B ** (2 * m) - 1)], v)
    assert svc.ctx_misses == 3 and svc.ctx_hits == 0
    assert len(svc._ctxs) == 2              # LRU bound enforced
    svc.reduce([1], vs[-1])                 # most recent: hit
    assert svc.ctx_hits == 1
    svc.reduce([1], vs[0])                  # evicted: miss again
    assert svc.ctx_misses == 4


def test_service_input_validation():
    svc = ModArithService(m_limbs=4, batch_buckets=(2,))
    with pytest.raises(ValueError):
        svc.context(0)
    with pytest.raises(OverflowError):
        svc.context(B ** 4)
    with pytest.raises(OverflowError):
        svc.reduce([B ** 8], 7)


def test_empty_requests_are_served_without_compute():
    """[] in -> [] out, no chunks planned, no precompute, no compile
    (the n=0 path must never touch the device)."""
    assert BT.Batcher((4,)).plan(0) == []
    assert BT.Batcher((4,)).plan(-3) == []
    svc = ModArithService(m_limbs=4, e_limbs=1, batch_buckets=(2,),
                          capture_profiles=False)
    assert svc.reduce([], 7) == []
    assert svc.modmul([], [], 7) == []
    assert svc.modexp([], [], 7) == []
    assert svc.ctx_misses == 0              # no precompute for nothing
    assert svc._fns.misses == 0             # no executable compiled
    assert svc.telemetry.stats()["requests"] == {}

    from repro.serving.bigint_service import BigintDivisionService
    div = BigintDivisionService(m_limbs=4, batch_buckets=(2,),
                                capture_profiles=False)
    assert div.divide([], []) == ([], [])
    assert div._fns.misses == 0


def test_validation_rejects_types_and_ranges_with_index():
    """Hardened validation: every operand is range-checked against the
    op's schema BEFORE any device work, errors carry the offending
    index, and non-ints (including bools) are rejected uniformly."""
    from repro.serving import errors as E
    svc = ModArithService(m_limbs=2, e_limbs=1, batch_buckets=(2,),
                          capture_profiles=False)
    v = 1000003
    # type errors carry the column name and index
    with pytest.raises(TypeError, match=r"a\[1\].*float"):
        svc.modmul([1, 2.5], [3, 4], v)
    with pytest.raises(TypeError, match=r"x\[0\].*bool"):
        svc.reduce([True], v)
    with pytest.raises(TypeError, match="modulus"):
        svc.reduce([1], 7.0)
    # range errors too (negative and too-large; modmul bound is B^m)
    with pytest.raises(OverflowError, match=r"b\[2\]"):
        svc.modmul([1, 1, 1], [0, 0, B ** 2], v)
    with pytest.raises(OverflowError, match=r"x\[1\]"):
        svc.reduce([5, -1], v)
    # exponents are bounded by e_limbs storage, not the modulus width
    with pytest.raises(OverflowError, match=r"e\[0\]"):
        svc.modexp([1], [B], v)
    svc.modexp([1], [B - 1], v)             # in e_limbs range: fine
    # mismatched column lengths name both columns
    with pytest.raises(ValueError, match=r"len\(a\) = 2.*len\(b\) = 1"):
        svc.modmul([1, 2], [3], v)
    # one modulus per row: the count must match, each is checked
    with pytest.raises(ValueError, match=r"len\(x\) = 2.*len\(v\) = 1"):
        svc.reduce([1, 2], [v])
    with pytest.raises(ValueError, match=r"v\[1\] must be positive"):
        svc.reduce([1, 2], [v, 0])
    with pytest.raises(TypeError, match=r"v\[0\].*float"):
        svc.reduce([1], [7.0])
    # everything above was rejected before compute
    assert svc._fns.misses <= 1             # only the valid modexp
    assert svc.ctx_misses <= 1

    from repro.serving.bigint_service import BigintDivisionService
    div = BigintDivisionService(m_limbs=2, batch_buckets=(2,),
                                capture_profiles=False)
    with pytest.raises(TypeError, match=r"u\[0\]"):
        div.divide(["9"], [3])
    with pytest.raises(OverflowError, match=r"v\[1\]"):
        div.divide([1, 2], [3, B ** 2])
    with pytest.raises(ValueError, match="mismatched"):
        div.divide([1, 2], [3])
    # all typed errors are serving-taxonomy InvalidRequest subtypes
    with pytest.raises(E.InvalidRequest):
        div.divide([1], [-1])


def test_service_same_ladder_different_exponents():
    """Padding exponents of different bit lengths must stay exact
    (constant trip count, where-masked windows)."""
    m = 4
    svc = ModArithService(m_limbs=m, e_limbs=2, batch_buckets=(4,))
    v = 1000003
    a = [2, 3, 5, 7]
    e = [0, 1, 65535, 2 ** 31 - 1]
    assert svc.modexp(a, e, v) == [pow(x, y, v) for x, y in zip(a, e)]


# ---------------------------------------------------------------------------
# per-row moduli: many keys in one call, against Python's pow / %
# ---------------------------------------------------------------------------

def _keys(rnd, n, m):
    return [rnd.randint(2, B ** m - 1) | 1 for _ in range(n)]


def _rows(rnd, op, moduli, m, em=2):
    """Request columns of `op` for rows with these moduli, and the
    reference answers (Python's pow and %)."""
    if op == "reduce":
        xs = [rnd.randint(0, B ** (2 * m) - 1) for _ in moduli]
        return [xs], [x % v for x, v in zip(xs, moduli)]
    a = [rnd.randint(0, B ** m - 1) for _ in moduli]
    if op == "modmul":
        b = [rnd.randint(0, B ** m - 1) for _ in moduli]
        return [a, b], [(x * y) % v for x, y, v in zip(a, b, moduli)]
    e = [rnd.randint(0, B ** em - 1) for _ in moduli]
    return [a, e], [pow(x, y, v) for x, y, v in zip(a, e, moduli)]


@pytest.mark.parametrize("op", ["reduce", "modmul", "modexp"])
def test_per_row_moduli_over_many_keys_match_the_reference(op):
    """11 rows over 5 seeded keys in one call: two bucket chunks, each
    row reduced by its own modulus, every key precomputed once."""
    rnd = random.Random(40)
    m = 4
    svc = ModArithService(m_limbs=m, e_limbs=2, impl="blocked",
                          batch_buckets=(8,), capture_profiles=False)
    keys = _keys(rnd, 5, m)
    moduli = [rnd.choice(keys) for _ in range(11)]
    cols, want = _rows(rnd, op, moduli, m)
    assert getattr(svc, op)(*cols, moduli) == want
    st = svc.stats()
    assert st["bucket_seconds"][f"{op}/b8"]["count"] == 2
    assert svc.ctx_misses == len(set(moduli))
    calls, = svc.telemetry.registry.get("call_moduli").series()
    assert calls.value == len(set(moduli[:8])) + len(set(moduli[8:]))


def test_one_key_is_the_single_modulus_case():
    """One modulus for every row, given once or once per row, is the
    same call: the same answers, one context, one slot."""
    rnd = random.Random(41)
    m = 4
    svc = ModArithService(m_limbs=m, e_limbs=2, impl="blocked",
                          batch_buckets=(4,), capture_profiles=False)
    v = _keys(rnd, 1, m)[0]
    (a, e), want = _rows(rnd, "modexp", [v] * 6, m)
    assert svc.modexp(a, e, v) == want
    assert svc.modexp(a, e, [v] * 6) == want
    assert (svc.ctx_misses, svc.ctx_hits) == (1, 3)   # chunks (4, 2) x 2
    assert svc.stats()["ctx_cache"]["size"] == 1


@pytest.mark.parametrize("slots,calls", [(1, 6), (3, 2), (6, 1)])
def test_more_distinct_moduli_than_slots_still_answer_exactly(slots, calls):
    """8 rows over 6 keys in one bucket chunk: with fewer slots than
    keys the chunk is split into parts of at most `slots` keys, each
    its own bucket call, and the slab never holds more than it has."""
    rnd = random.Random(42)
    m = 4
    svc = ModArithService(m_limbs=m, e_limbs=1, impl="blocked",
                          batch_buckets=(2, 8), max_cached_moduli=slots,
                          capture_profiles=False)
    keys = _keys(rnd, 6, m)
    moduli = keys + keys[:2]
    rnd.shuffle(moduli)
    (xs,), want = _rows(rnd, "reduce", moduli, m)
    assert svc.reduce(xs, moduli) == want
    st = svc.stats()
    assert sum(h["count"] for h in st["bucket_seconds"].values()) == calls
    assert st["ctx_cache"]["size"] == slots
    assert svc.ctx_misses == 6 and svc.ctx_evictions == 6 - slots


def test_eviction_between_calls_keeps_answers_exact():
    """A 2-slot slab over 3 keys: each call's cold key takes the least
    recently used slot of a key the call does not name."""
    rnd = random.Random(43)
    m = 4
    svc = ModArithService(m_limbs=m, e_limbs=1, impl="blocked",
                          batch_buckets=(4,), max_cached_moduli=2,
                          capture_profiles=False)
    k0, k1, k2 = _keys(rnd, 3, m)
    for moduli, hits, misses in [([k0, k1, k0], 0, 2),
                                 ([k1, k2], 1, 1),      # evicts k0
                                 ([k0, k2, k2], 1, 1),  # evicts k1
                                 ([k1], 0, 1)]:         # evicts k2
        h0, m0 = svc.ctx_hits, svc.ctx_misses
        (a, b), want = _rows(rnd, "modmul", moduli, m)
        assert svc.modmul(a, b, moduli) == want
        assert (svc.ctx_hits - h0, svc.ctx_misses - m0) == (hits, misses)
    assert svc.ctx_evictions == 3
    assert list(svc._ctxs) == [k0, k1]


def test_a_failed_precompute_leaves_no_context_behind():
    """A batched precompute that raises frees the slots its cold moduli
    took, so the next call computes them again and answers exactly."""
    rnd = random.Random(44)
    m = 4
    svc = ModArithService(m_limbs=m, e_limbs=1, impl="blocked",
                          batch_buckets=(4,), max_cached_moduli=2,
                          capture_profiles=False)
    k0, k1, k2 = _keys(rnd, 3, m)
    (a, b), want = _rows(rnd, "modmul", [k0, k1], m)
    assert svc.modmul(a, b, [k0, k1]) == want
    fill = svc._fill

    def broken(cold):
        raise RuntimeError("device lost")
    svc._fill = broken
    with pytest.raises(RuntimeError, match="device lost"):
        svc.modmul(a, b, [k0, k2])               # k2 cold, evicts k1
    assert list(svc._ctxs) == [k0] and svc._free == [1]
    svc._fill = fill
    (a, b), want = _rows(rnd, "modmul", [k2, k1, k0], m)
    assert svc.modmul(a[:2], b[:2], [k2, k1]) == want[:2]
    assert svc.modmul(a[2:], b[2:], [k0]) == want[2:]
