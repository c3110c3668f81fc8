"""Core whole-shifted-inverse division: oracle + JAX implementation."""

import random

import numpy as np
import jax.numpy as jnp
import pytest
from _hyp import given, settings, st

from repro.core import bigint as bi, pyref as R, shinv as S

B = bi.BASE


# ---------------------------------------------------------------------------
# pyref oracle vs Python ints
# ---------------------------------------------------------------------------

def test_paper_examples():
    q, r = R.divmod_shinv(314159265358979, 27183, 10)
    assert (q, r) == divmod(314159265358979, 27183)
    assert q == 11557196238
    q, r = R.divmod_shinv(726319138718412, 27183, 10)
    assert q == 26719609267            # the delta=+1 case from Example 2


def test_pyref_shinv_exhaustive_small():
    for v in range(1, 4096, 3):
        for h in (1, 2, 3, 5):
            w = R.shinv(v, h, 16)
            exact = 16 ** h // v
            assert w in (exact, exact + 1), (v, h)


@given(st.integers(0, 2 ** 512), st.integers(1, 2 ** 256))
@settings(max_examples=200, deadline=None)
def test_pyref_div_property(u, v):
    assert R.divmod_shinv(u, v, B) == divmod(u, v)


@given(st.integers(1, 2 ** 300), st.integers(1, 60))
@settings(max_examples=150, deadline=None)
def test_pyref_shinv_theorem2(v, h):
    """shinv_h(v) in {floor(B^h/v), floor(B^h/v) + 1} (Theorem 2)."""
    w = R.shinv(v, h, B)
    exact = B ** h // v
    assert w in (exact, exact + 1)


def test_pyref_small_bases():
    rnd = random.Random(7)
    for base in (2, 3, 4, 10):
        for _ in range(100):
            v = rnd.randint(1, base ** 12)
            h = rnd.randint(1, 16)
            w = R.shinv(v, h, base)
            exact = base ** h // v
            assert w in (exact, exact + 1), (base, v, h)


def test_cost_model_bounds():
    """Sec 2.3: division needs >= 5 full multiplications; the fixed
    trip-count Refine (paper Algorithm 1 line 19) occasionally runs one
    settling iteration extra, so allow a small tail above 7."""
    rnd = random.Random(11)
    M = 256
    counts = []
    for _ in range(50):
        u = rnd.randint(B ** (M - 3), B ** (M - 2) - 1)
        kv = rnd.randint(2, M // 2)
        v = rnd.randint(B ** (kv - 1), B ** kv - 1)
        c = R.CostCounter()
        assert R.divmod_shinv(u, v, B, c) == divmod(u, v)
        n = c.n_full_mults(M)
        n += sum(1 for rec in c.records
                 if rec.where == "div-u*shinv" and rec.prec_out > M)
        counts.append(n)
    assert min(counts) >= 5
    assert sorted(counts)[len(counts) // 2] <= 7      # median within bound
    assert max(counts) <= 9


# ---------------------------------------------------------------------------
# host int <-> limb conversion vs the per-limb shift loops
# ---------------------------------------------------------------------------

def _shift_from_int(x, m):
    """Reference: one 16-bit limb per step (the original loop)."""
    if x < 0:
        raise ValueError("unsigned representation only")
    out = np.zeros(m, dtype=np.uint32)
    i = 0
    while x:
        if i >= m:
            raise OverflowError("value does not fit in m limbs")
        out[i] = x & bi.MASK
        x >>= bi.LOG_BASE
        i += 1
    return out


def _shift_to_int(limbs):
    x = 0
    for d in np.asarray(limbs, dtype=np.uint64)[::-1]:
        x = (x << bi.LOG_BASE) | int(d)
    return x


@pytest.mark.parametrize("m", [1, 2, 128, 2048, 16384])
def test_int_limb_roundtrip_matches_shift_loops(m):
    rnd = random.Random(m)
    xs = [0, 1, B ** m - 1,
          rnd.randrange(B ** (m // 2 + 1)),        # leading zero limbs
          rnd.randrange(B ** m), rnd.randrange(B ** m)]
    ref = np.stack([_shift_from_int(x, m) for x in xs])

    arr = bi.batch_from_ints(xs, m)
    assert arr.dtype == np.uint32 and arr.shape == (len(xs), m)
    np.testing.assert_array_equal(arr, ref)
    assert bi.batch_to_ints(arr) == xs == [_shift_to_int(r) for r in ref]
    for x, row in zip(xs, ref):
        one = bi.from_int(x, m)
        assert one.dtype == np.uint32 and one.shape == (m,)
        np.testing.assert_array_equal(one, row)
        assert bi.to_int(row) == x
    assert bi.to_int(jnp.asarray(ref[-1])) == xs[-1]
    assert bi.to_int([int(d) for d in ref[-1]]) == xs[-1]

    empty = bi.batch_from_ints([], m)
    assert empty.dtype == np.uint32 and empty.shape == (0, m)
    assert bi.batch_to_ints(empty) == []

    with pytest.raises(ValueError, match="^unsigned representation only$"):
        bi.from_int(-1, m)
    with pytest.raises(ValueError, match="^unsigned representation only$"):
        bi.batch_from_ints([1, -rnd.randrange(1, B ** m)], m)
    with pytest.raises(OverflowError,
                       match="^value does not fit in m limbs$"):
        bi.from_int(B ** m, m)
    with pytest.raises(OverflowError,
                       match="^value does not fit in m limbs$"):
        bi.batch_from_ints([0, B ** m + rnd.randrange(B ** m)], m)
    with pytest.raises(ValueError, match="^unsigned representation only$"):
        bi.to_int([-1] + [0] * (m - 1))

    # hand-built limbs >= B read as the exact sum of d_i * B^i
    wide = np.array([rnd.choice([B, B + 1, 2 ** 32 - 1, rnd.randrange(B)])
                     for _ in range(m)], dtype=np.uint32)
    lo, hi = wide & bi.MASK, wide >> bi.LOG_BASE       # d_i = lo_i + hi_i B
    exact = _shift_to_int(lo) + (_shift_to_int(hi) << bi.LOG_BASE)
    assert bi.to_int(wide) == exact
    assert bi.batch_to_ints(np.stack([wide, ref[-1]])) == [exact, xs[-1]]
    big = np.array([2 ** 64 - 1] + [0] * (m - 1), dtype=np.uint64)
    assert bi.to_int(big) == 2 ** 64 - 1


# ---------------------------------------------------------------------------
# JAX implementation vs oracle
# ---------------------------------------------------------------------------

def _check_batch(us, vs, m):
    q, r = S.divmod_batch(jnp.asarray(bi.batch_from_ints(us, m)),
                          jnp.asarray(bi.batch_from_ints(vs, m)))
    for u, v, qq, rr in zip(us, vs, bi.batch_to_ints(q), bi.batch_to_ints(r)):
        assert (qq, rr) == divmod(u, v), (u, v)


def test_jax_div_edges():
    us, vs = [], []
    for u in [0, 1, 2, B - 1, B, B + 1, B * B, B * B - 1, B ** 3]:
        for v in [1, 2, 3, B - 1, B, B + 1, B * B - 1, B * B]:
            us.append(u), vs.append(v)
    _check_batch(us, vs, 4)


@pytest.mark.parametrize("m", [4, 8, 32])
def test_jax_div_random(m):
    rnd = random.Random(m)
    us = [rnd.randint(0, B ** rnd.randint(1, m) - 1) for _ in range(48)]
    vs = [rnd.randint(1, B ** rnd.randint(1, m) - 1) for _ in range(48)]
    _check_batch(us, vs, m)


def test_jax_div_bench_config():
    """The paper's evaluation configuration: prec(u) = M-2, prec(v)
    random in [2, M/2] -- maximal refinement iterations."""
    rnd = random.Random(42)
    m = 64
    us = [rnd.randint(B ** (m - 3), B ** (m - 2) - 1) for _ in range(24)]
    vs = []
    for _ in range(24):
        kv = rnd.randint(2, m // 2)
        vs.append(rnd.randint(B ** (kv - 1), B ** kv - 1))
    _check_batch(us, vs, m)


@given(st.data())
@settings(max_examples=40, deadline=None)
def test_jax_div_property(data):
    m = data.draw(st.sampled_from([4, 8, 16]))
    u = data.draw(st.integers(0, B ** m - 1))
    v = data.draw(st.integers(1, B ** m - 1))
    _check_batch([u], [v], m)


def test_jax_shinv_matches_pyref():
    rnd = random.Random(5)
    m = 16
    width = m + 8
    import math
    from repro.core.shinv import shinv_batch
    vs, hs = [], []
    for _ in range(32):
        kv = rnd.randint(1, m)
        vs.append(rnd.randint(1, B ** kv - 1))
        hs.append(rnd.randint(1, m))
    w = shinv_batch(jnp.asarray(bi.batch_from_ints(vs, width)),
                    jnp.asarray(np.array(hs, np.int32)),
                    iters_max=math.ceil(math.log2(m)) + 2)
    for v, h, wi in zip(vs, hs, bi.batch_to_ints(w)):
        exact = B ** h // v
        assert wi in (exact, exact + 1), (v, h, wi, exact)
