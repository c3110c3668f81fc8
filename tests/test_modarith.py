"""Barrett modular arithmetic on the cached shifted inverse: exactness
vs Python ints at multiple precisions, edge cases, impl dispatch."""

import random
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hyp import given, settings, st
from repro.core import bigint as bi
from repro.core import modarith as MA
from repro.core import pyref
from repro.core import shinv as S

B = bi.BASE


def _ctx(v, m, **kw):
    return MA.barrett_precompute(jnp.asarray(bi.from_int(v, m)), **kw)


def _one_slot(ctx):
    """A one-slot context slab holding ctx."""
    return MA.ContextSlab(*(c[None] for c in ctx))


def _reduce(ctx, x, m, **kw):
    return bi.to_int(MA.barrett_reduce(
        ctx, jnp.asarray(bi.from_int(x, 2 * m)), **kw))


# ---------------------------------------------------------------------------
# barrett_reduce: exact at >= 3 precisions, vs Python % and divmod_fixed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [4, 16, 32])      # 64 / 256 / 512 bits
def test_reduce_random(m):
    rnd = random.Random(m)
    for _ in range(8):
        v = rnd.randint(1, B ** m - 1)
        x = rnd.randint(0, B ** (2 * m) - 1)
        ctx = _ctx(v, m)
        assert _reduce(ctx, x, m) == x % v, (m, v, x)


def test_reduce_matches_divmod_fixed():
    """Same remainder as the division subsystem on the same operands."""
    rnd = random.Random(0)
    m = 8
    v = rnd.randint(1, B ** m - 1)
    ctx = _ctx(v, m)
    for _ in range(4):
        x = rnd.randint(0, B ** (2 * m) - 1)
        xw = jnp.asarray(bi.from_int(x, 2 * m))
        vw = jnp.asarray(bi.from_int(v, 2 * m))
        _, r_div = S.divmod_fixed(xw, vw)
        r_bar = MA.barrett_reduce(ctx, xw)
        assert bi.to_int(r_bar) == bi.to_int(r_div) == x % v


def test_reduce_edge_cases():
    m = 4
    # v a power of B (shinv special case), v single-limb, v = 1
    for v in (1, 7, B - 1, B, B ** 2, B ** 3, B ** 4 - 1):
        ctx = _ctx(v, m)
        for x in (0, 1, v - 1, v, v + 1, B ** 5, B ** (2 * m) - 1):
            assert _reduce(ctx, x, m) == x % v, (v, x)


def test_reduce_identity_below_modulus():
    """x < v: the reduction is the identity."""
    rnd = random.Random(1)
    m = 8
    for _ in range(4):
        v = rnd.randint(2, B ** m - 1)
        x = rnd.randint(0, v - 1)
        assert _reduce(_ctx(v, m), x, m) == x


def test_reduce_rejects_oversized_input():
    ctx = _ctx(7, 4)
    with pytest.raises(ValueError):
        MA.barrett_reduce(ctx, jnp.zeros((9,), jnp.uint32))


# ---------------------------------------------------------------------------
# modmul / modexp vs Python pow at >= 3 precisions, both impls
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [4, 16, 32])
def test_modmul_random(m):
    rnd = random.Random(m + 100)
    for _ in range(6):
        v = rnd.randint(1, B ** m - 1)
        a = rnd.randint(0, B ** m - 1)
        b = rnd.randint(0, B ** m - 1)
        got = bi.to_int(MA.modmul(_ctx(v, m),
                                  jnp.asarray(bi.from_int(a, m)),
                                  jnp.asarray(bi.from_int(b, m))))
        assert got == (a * b) % v, (m, v, a, b)


def test_modmul_batch_pallas_batched():
    """impl dispatch to the natively batched kernel: the per-row
    context vmap hands whole batches to one launch (custom_vmap rule);
    every row names the one slot."""
    rnd = random.Random(51)
    m = 4
    v = rnd.randint(B ** (m - 1), B ** m - 1)
    slab = _one_slot(_ctx(v, m, impl="pallas_batched"))
    aa = [rnd.randint(0, B ** m - 1) for _ in range(4)]
    bb = [rnd.randint(0, B ** m - 1) for _ in range(4)]
    out = jax.jit(partial(MA.modmul_rows, impl="pallas_batched"))(
        slab, jnp.zeros((4,), jnp.int32),
        jnp.asarray(bi.batch_from_ints(aa, m)),
        jnp.asarray(bi.batch_from_ints(bb, m)))
    for a, b, o in zip(aa, bb, bi.batch_to_ints(out)):
        assert o == (a * b) % v


@pytest.mark.parametrize("impl", ["scan", "blocked"])
@pytest.mark.parametrize("m", [4, 16])          # 64 / 256 bits
def test_modexp_vs_pow(impl, m):
    rnd = random.Random(m * 7 + len(impl))
    for _ in range(3):
        v = rnd.randint(2, B ** m - 1)
        a = rnd.randint(0, B ** m - 1)
        e = rnd.randint(0, B ** 2 - 1)          # 32-bit exponents
        ctx = _ctx(v, m, impl=impl)
        got = bi.to_int(MA.modexp(ctx, jnp.asarray(bi.from_int(a, m)),
                                  jnp.asarray(bi.from_int(e, 2)),
                                  impl=impl))
        assert got == pow(a, e, v), (impl, m, v, a, e)


def test_modexp_exponent_edges():
    m = 4
    rnd = random.Random(3)
    for v in (1, 97, B ** 2, B ** m - 1):
        ctx = _ctx(v, m)
        a = rnd.randint(0, B ** m - 1)
        for e in (0, 1, 2, 3):
            got = bi.to_int(MA.modexp(
                ctx, jnp.asarray(bi.from_int(a, m)),
                jnp.asarray(bi.from_int(e, 1))))
            assert got == pow(a, e, v), (v, a, e)


@pytest.mark.parametrize("window_bits", [1, 2, 8])
def test_modexp_window_sizes(window_bits):
    m = 4
    v, a, e = 1000003, 987654321, 0xBEEF
    got = bi.to_int(MA.modexp(_ctx(v, m), jnp.asarray(bi.from_int(a, m)),
                              jnp.asarray(bi.from_int(e, 1)),
                              window_bits=window_bits))
    assert got == pow(a, e, v)


def test_modexp_rejects_bad_window():
    with pytest.raises(ValueError):
        MA.modexp(_ctx(7, 4), jnp.asarray(bi.from_int(3, 4)),
                  jnp.asarray(bi.from_int(1, 1)), window_bits=3)


# ---------------------------------------------------------------------------
# batched entry points
# ---------------------------------------------------------------------------

def test_batched_per_instance_moduli():
    rnd = random.Random(9)
    m, em, n = 8, 2, 5
    vs = [rnd.randint(1, B ** m - 1) for _ in range(n)]
    xs = [rnd.randint(0, B ** (2 * m) - 1) for _ in range(n)]
    az = [rnd.randint(0, B ** m - 1) for _ in range(n)]
    bz = [rnd.randint(0, B ** m - 1) for _ in range(n)]
    es = [rnd.randint(0, B ** em - 1) for _ in range(n)]
    r = MA.reduce_batch(jnp.asarray(bi.batch_from_ints(xs, 2 * m)),
                        jnp.asarray(bi.batch_from_ints(vs, m)))
    assert bi.batch_to_ints(np.asarray(r)) == [x % v for x, v in zip(xs, vs)]
    mm = MA.modmul_batch(jnp.asarray(bi.batch_from_ints(az, m)),
                         jnp.asarray(bi.batch_from_ints(bz, m)),
                         jnp.asarray(bi.batch_from_ints(vs, m)))
    assert bi.batch_to_ints(np.asarray(mm)) == \
        [(a * b) % v for a, b, v in zip(az, bz, vs)]
    me = MA.modexp_batch(jnp.asarray(bi.batch_from_ints(az, m)),
                         jnp.asarray(bi.batch_from_ints(es, em)),
                         jnp.asarray(bi.batch_from_ints(vs, m)))
    assert bi.batch_to_ints(np.asarray(me)) == \
        [pow(a, e, v) for a, e, v in zip(az, es, vs)]


def test_shared_context_batch():
    rnd = random.Random(11)
    m, em, n = 8, 2, 4
    v = rnd.randint(2, B ** m - 1)
    slab = _one_slot(_ctx(v, m))
    az = [rnd.randint(0, B ** m - 1) for _ in range(n)]
    es = [rnd.randint(0, B ** em - 1) for _ in range(n)]
    me = jax.jit(MA.modexp_rows)(slab, jnp.zeros((n,), jnp.int32),
                                 jnp.asarray(bi.batch_from_ints(az, m)),
                                 jnp.asarray(bi.batch_from_ints(es, em)))
    assert bi.batch_to_ints(np.asarray(me)) == [pow(a, e, v) for a, e
                                                in zip(az, es)]


@pytest.mark.slow
def test_reduce_4096bit():
    """One large-precision pass: 4096-bit modulus, 8192-bit operand."""
    rnd = random.Random(42)
    m = 256
    v = rnd.randint(B ** (m - 1), B ** m - 1)
    x = rnd.randint(0, B ** (2 * m) - 1)
    assert _reduce(_ctx(v, m), x, m) == x % v


@given(st.integers(0, B ** 8 - 1), st.integers(0, B ** 8 - 1),
       st.integers(1, B ** 8 - 1))
@settings(max_examples=20, deadline=None)
def test_reduce_property(x_lo, x_hi, v):
    m = 8
    x = x_hi * B ** 8 + x_lo                 # x < B^(2m), reduce's domain
    assert _reduce(_ctx(v, m), x, m) == x % v


# ---------------------------------------------------------------------------
# per-row contexts: the serving layer's slab
# ---------------------------------------------------------------------------

def test_batched_precompute_is_bit_equal_to_per_key():
    """One batched precompute into slab slots (padding repeats a row)
    gives each key the context a single-key precompute gives, and its
    mu is the reference's shinv (core/pyref.py), floor(B^h / v) +
    lambda with lambda in {0, 1} (Theorem 2)."""
    rnd = random.Random(17)
    m, slots = 4, 8
    h = MA.barrett_h(m)
    keys = [rnd.randint(2, B ** m - 1) for _ in range(4)] + [3, B ** m - 1]
    at = [6, 0, 3, 7, 1, 4]
    pad = 8
    vs = jnp.asarray(bi.batch_from_ints(keys + [keys[0]] * (pad - 6), m))
    idx = jnp.asarray(at + [at[0]] * (pad - 6), jnp.int32)
    slab = jax.jit(MA.precompute_into)(MA.empty_slab(slots, m), vs, idx)
    precompute = jax.jit(MA.barrett_precompute)
    for key, s in zip(keys, at):
        one = precompute(jnp.asarray(bi.from_int(key, m)))
        assert bi.to_int(np.asarray(slab.v[s])) == key
        assert np.array_equal(np.asarray(slab.mu[s]), np.asarray(one.mu))
        assert int(slab.k[s]) == int(one.k)
        mu = bi.to_int(np.asarray(slab.mu[s]))
        assert mu == pyref.shinv(key, h, B)
        assert mu - B ** h // key in (0, 1)
    untouched = sorted(set(range(slots)) - set(at))
    assert not np.asarray(slab.mu)[untouched].any()
