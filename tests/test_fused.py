"""Fused division-step kernels (impl="pallas_fused") vs the reference
composition: bit-equivalence across the windowed Refine schedule, the
zero-divisor contract, and the structural launch-count guarantees.

CPU runs the kernels in Pallas interpret mode, which is slow per
launch; configurations here are chosen so compiled executables are
reused across tests (same shapes/statics hit the jit cache).
"""

import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hyp import given, settings, st

from repro.core import bigint as bi
from repro.core import modarith as MA
from repro.core import shinv as S
from repro.kernels import ops as K
from repro.kernels import fused as F
from repro.utils import jaxpr_stats as JS

B = bi.BASE


def _operands(m, batch, seed):
    """Random operands with the adversarial edges packed into the
    leading lanes (all-0xFFFF, power-of-B divisor, u=0, tiny)."""
    rnd = random.Random(seed)
    us = [rnd.randint(0, B ** m - 1) for _ in range(batch)]
    vs = [rnd.randint(1, B ** m - 1) for _ in range(batch)]
    edges = [(B ** m - 1, B ** (m // 2) - 1),   # all-0xFFFF u, 0xFFFF v
             (B ** m - 1, B ** m - 1),          # both all-0xFFFF
             (rnd.randint(0, B ** m - 1), B ** (m // 2)),  # v = B^k
             (0, 1), (B ** (m // 2), B ** m - 1), (5, 7)]
    for i, (uu, vv) in enumerate(edges[:batch]):
        us[i], vs[i] = uu, vv
    return us, vs


def _cmp_divmod(us, vs, m, windowed):
    u = jnp.asarray(bi.batch_from_ints(us, m))
    v = jnp.asarray(bi.batch_from_ints(vs, m))
    qf, rf = S.divmod_batch(u, v, impl="pallas_fused", windowed=windowed)
    qb, rb = S.divmod_batch(u, v, impl="blocked", windowed=windowed)
    np.testing.assert_array_equal(np.asarray(qf), np.asarray(qb))
    np.testing.assert_array_equal(np.asarray(rf), np.asarray(rb))
    for x, y, qq, rr in zip(us, vs, bi.batch_to_ints(qf),
                            bi.batch_to_ints(rf)):
        assert (qq, rr) == (divmod(x, y) if y else (0, x)), (x, y)


# ---------------------------------------------------------------------------
# divmod_fixed: fused vs unfused across batch sizes and windowed modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch,windowed,seed",
                         [(1, True, 0), (5, False, 1), (16, True, 2)])
def test_divmod_fused_equivalence(batch, windowed, seed):
    us, vs = _operands(4, batch, seed)
    _cmp_divmod(us, vs, 4, windowed)


@pytest.mark.slow
def test_divmod_fused_equivalence_windowed_schedule():
    """m = 26 limbs puts width above 32, so the windowed Refine
    actually iterates at win < W before growing to full width -- the
    fused kernels must be bit-identical across that schedule too."""
    us, vs = _operands(26, 5, 3)
    _cmp_divmod(us, vs, 26, True)


def test_divmod_zero_divisor_contract():
    """Satellite: divmod(u, 0) = (0, u) is DEFINED behavior on both
    paths (see shinv.py docstring; _initial_w0's maximum(V, 1) only
    keeps the traced division well-defined, the lane is masked)."""
    rnd = random.Random(7)
    m = 4
    us = [rnd.randint(0, B ** m - 1) for _ in range(16)]
    vs = [0 if i % 3 == 0 else rnd.randint(1, B ** m - 1)
          for i in range(16)]
    u = jnp.asarray(bi.batch_from_ints(us, m))
    v = jnp.asarray(bi.batch_from_ints(vs, m))
    for impl in ("blocked", "pallas_fused"):
        q, r = S.divmod_batch(u, v, impl=impl, windowed=True)
        for x, y, qq, rr in zip(us, vs, bi.batch_to_ints(q),
                                bi.batch_to_ints(r)):
            assert (qq, rr) == (divmod(x, y) if y else (0, x)), (impl, x, y)


def test_shinv_zero_divisor_contract():
    """Satellite: shinv_fixed(0, h) = 0 on both paths."""
    w = 12
    v = jnp.asarray(bi.batch_from_ints([0, 0, 37], w))
    h = jnp.asarray([6, 9, 6], jnp.int32)
    results = {}
    for impl in ("blocked", "pallas_fused"):
        si = S.shinv_batch(v, h, iters_max=4, impl=impl)
        assert bi.to_int(np.asarray(si)[0]) == 0, impl
        assert bi.to_int(np.asarray(si)[1]) == 0, impl
        # nonzero lane: shinv + lambda, lambda in {0, 1} (Theorem 2)
        assert bi.to_int(np.asarray(si)[2]) - B ** 6 // 37 in (0, 1), impl
        results[impl] = np.asarray(si)
    np.testing.assert_array_equal(results["blocked"],
                                  results["pallas_fused"])


# ---------------------------------------------------------------------------
# _step: direct fused vs reference equivalence on synthetic states
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("win", [8, 16])
def test_fused_step_matches_reference(win):
    """K.fused_step computes the same pure function on ANY input (not
    just valid Newton states): random iterates, scalars spanning the
    Refine ranges, inactive lanes, zero/all-0xFFFF edges."""
    rnd = random.Random(win)
    w_full, batch, g = 16, 8, 2
    vs = [B ** w_full - 1, 0] + [rnd.randint(0, B ** w_full - 1)
                                 for _ in range(batch - 2)]
    ws = [B ** win - 1, 0] + [rnd.randint(0, B ** win - 1)
                              for _ in range(batch - 2)]
    v = jnp.asarray(bi.batch_from_ints(vs, w_full))
    w = jnp.asarray(bi.batch_from_ints(ws, w_full))
    ls = jnp.asarray([rnd.randint(2, 5) for _ in range(batch)], jnp.int32)
    ms = jnp.asarray([rnd.randint(0, 3) for _ in range(batch)], jnp.int32)
    hs = jnp.asarray([rnd.randint(1, 2 * win - 1) for _ in range(batch)],
                     jnp.int32)
    ss = jnp.asarray([rnd.randint(0, 2) for _ in range(batch)], jnp.int32)
    act = jnp.asarray([i % 3 != 0 for i in range(batch)])

    def run(impl):
        fn = jax.jit(jax.vmap(
            lambda vv, ww, hh, mm, ll, sc, aa: K.fused_step(
                vv, ww, h=hh, m=mm, l=ll, s=sc, active=aa, g=g, win=win,
                impl=impl)))
        return fn(v, w, hs, ms, ls, ss, act)

    np.testing.assert_array_equal(np.asarray(run("pallas_fused")),
                                  np.asarray(run("blocked")))


# ---------------------------------------------------------------------------
# barrett_reduce: fused vs unfused, shared-context batches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", [1, 5, 16])
def test_barrett_fused_equivalence(batch):
    rnd = random.Random(batch)
    m = 4
    v = rnd.randint(2, B ** m - 1)
    ctx = MA.barrett_precompute(jnp.asarray(bi.from_int(v, m)),
                                impl="blocked")
    xs = [rnd.randint(0, B ** (2 * m) - 1) for _ in range(batch)]
    edges = [B ** (2 * m) - 1, 0, v, v - 1, v + 1, B ** m]
    for i, e in enumerate(edges[:batch]):
        xs[i] = e
    x = jnp.asarray(bi.batch_from_ints(xs, 2 * m))
    rf = MA.reduce_shared_batch(ctx, x, impl="pallas_fused")
    rb = MA.reduce_shared_batch(ctx, x, impl="blocked")
    np.testing.assert_array_equal(np.asarray(rf), np.asarray(rb))
    for xx, got in zip(xs, bi.batch_to_ints(rf)):
        assert got == xx % v, (xx, v)


# ---------------------------------------------------------------------------
# structural guarantees: launch counts straight off the traced jaxpr
# ---------------------------------------------------------------------------

def test_fused_launch_counts():
    """The fusion contract, backend-independent: one Refine iteration
    <= 2 Pallas launches, finalization and Barrett one each, a full
    divmod_batch exactly 2*iters + 1."""
    w_full, win = 16, 16
    v = jnp.zeros((3, w_full), jnp.uint32)
    h = jnp.zeros((3,), jnp.int32)

    def step(vv, ww):
        return jax.vmap(lambda a, b: K.fused_step(
            a, b, h=jnp.int32(5), m=jnp.int32(1), l=jnp.int32(2),
            s=jnp.int32(0), active=jnp.bool_(True), g=2, win=win,
            impl="pallas_fused"))(vv, ww)
    n, _ = JS.trace_counts(step, v, v)
    assert n == F.FUSED_STEP_LAUNCHES == 2

    def corr(u, vv, si, hh):
        return jax.vmap(lambda a, b, c, d: K.fused_correct(
            a, b, c, h=d, impl="pallas_fused"))(u, vv, si, hh)
    n, _ = JS.trace_counts(corr, v, v, v, h)
    assert n == F.FUSED_CORRECT_LAUNCHES == 1

    def barr(x, mu, vv):
        return jax.vmap(lambda a, b, c: K.fused_barrett(
            a, b, c, h=10, impl="pallas_fused"))(x, mu, vv)
    n, _ = JS.trace_counts(barr, v, v, v)
    assert n == F.FUSED_BARRETT_LAUNCHES == 1

    # whole batched division: 2 launches per iteration + 1 finalization
    m = 4
    iters = S.refine_iters(m)
    u4 = jnp.zeros((3, m), jnp.uint32)
    n, _ = JS.trace_counts(
        lambda a, b: S.divmod_batch(a, b, impl="pallas_fused"), u4, u4)
    assert n == 2 * iters + 1

    # the unfused composition keeps its glue in XLA: strictly more eqns
    _, ops_fused = JS.trace_counts(
        lambda a, b: S.divmod_batch(a, b, impl="pallas_fused"), u4, u4)
    _, ops_ref = JS.trace_counts(
        lambda a, b: S.divmod_batch(a, b, impl="blocked"), u4, u4)
    assert ops_ref > ops_fused


def _kernel_names(fn, *args) -> list[str]:
    """The `name` of every pallas_call in fn's jaxpr, in launch order."""
    jx = jax.make_jaxpr(fn)(*args)
    return [e.params["name"]
            for e in JS.iter_eqns(jx.jaxpr, into_kernels=False)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("threshold,gen", [(None, ""), (1, "_grid")])
def test_kernels_are_named_by_stage_in_launch_order(threshold, gen):
    """Each launch carries its stage's name (the instruction's name in
    the compiled program and in a device profile): Refine iteration i
    at window w as refine_i<ii>_w<w>_powdiff / _update, then the
    finalization; `_grid` marks the grid generation."""
    m = 4
    w = m + S.PAD                             # every window clips to w
    u = jnp.zeros((3, m), jnp.uint32)
    K.set_fused_grid_threshold(threshold)
    try:
        names = _kernel_names(
            lambda a, b: S.divmod_batch(a, b, impl="pallas_fused"), u, u)
        iters = S.refine_iters(m)
        assert len(names) == 2 * iters + 1
        assert names == [f"refine_i{i:02d}_w{w}_{stage}{gen}"
                         for i in range(iters)
                         for stage in ("powdiff", "update")] + \
            [f"divmod_correct{gen}"]
        ctx = MA.BarrettContext(v=jnp.zeros((m,), jnp.uint32),
                                mu=jnp.zeros((MA.barrett_width(m),),
                                             jnp.uint32),
                                k=jnp.zeros((), jnp.int32))
        x = jnp.zeros((3, 2 * m), jnp.uint32)
        assert _kernel_names(lambda c, a: MA.reduce_shared(
            c, a, impl="pallas_fused"), ctx, x) == [f"barrett{gen}"]
    finally:
        K.set_fused_grid_threshold(None)


def test_products_are_named():
    u = jnp.zeros((3, 8), jnp.uint32)
    for impl, name in (("pallas", "bigmul"),
                       ("pallas_batched", "bigmul_batched")):
        names = _kernel_names(lambda a, b: jax.vmap(
            lambda x, y: K.mul(x, y, 16, impl=impl))(a, b), u, u)
        assert names and set(names) == {name}, (impl, names)


def test_kernel_plan_records_fused_geometry():
    from repro.serving import batching as BT
    plan = BT.kernel_plan(16, 16, "pallas_fused")
    assert plan.fused and plan.step_launches == 2 and plan.step_glue_ops == 0
    plan = BT.kernel_plan(16, 16, "pallas_batched")
    assert not plan.fused and plan.step_launches == 2
    assert plan.step_glue_ops == F.UNFUSED_STEP_GLUE_OPS
    plan = BT.kernel_plan(16, 16, "blocked")
    assert not plan.fused and plan.step_launches == 0
    assert plan.step_glue_ops == F.UNFUSED_STEP_GLUE_OPS


# ---------------------------------------------------------------------------
# satellite: the deduplicated carry-scan core
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(0, 3), min_size=1, max_size=48))
@settings(max_examples=60, deadline=None)
def test_carry_scan_shared_property(codes):
    """arith.carry_scan (now also the core of ops._resolve8) against a
    sequential reference over random generate/propagate patterns."""
    from repro.core import arith as A
    gen = [c & 1 for c in codes]
    prop = [(c >> 1) & 1 for c in codes]
    c = 0
    want = []
    for g_, p_ in zip(gen, prop):
        want.append(c)
        c = g_ | (p_ & c)
    got = A.carry_scan(jnp.asarray(gen, jnp.int32),
                       jnp.asarray(prop, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # batched, last axis: every row scans independently
    g2 = jnp.stack([jnp.asarray(gen, jnp.int32)] * 2)
    p2 = jnp.stack([jnp.asarray(prop, jnp.int32)] * 2)
    got2 = A.carry_scan(g2, p2, axis=-1)
    np.testing.assert_array_equal(np.asarray(got2),
                                  np.stack([np.asarray(want)] * 2))
