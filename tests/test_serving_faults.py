"""Failure-path coverage for the serving tier: exception taxonomy,
deterministic fault injection, retry/backoff, deadlines, circuit
breakers, kernel degradation, and the async frontend's accounting
contract (every admitted request gets a terminal answer).

Everything here is deterministic: faults come from seeded
`FaultSpec` plans, time comes from injectable fake clocks, and the
only real sleeps are the (millisecond-scaled) retry backoffs.
"""

import asyncio
import random
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from repro.core import bigint as bi
from repro.serving import batching as BT
from repro.serving import errors as E
from repro.serving.bigint_service import BigintDivisionService
from repro.serving.faults import FaultInjector, FaultSpec
from repro.serving.frontend import AsyncFrontend
from repro.serving.modexp_service import ModArithService
from repro.serving.policy import (CircuitBreaker, KernelLadder,
                                  ServingPolicy, backoff_delay)

B = bi.BASE

# fast-retry policy for frontend tests (delays in the 1 ms range)
FAST = dict(max_retries=3, backoff_base=0.001, backoff_cap=0.004,
            breaker_cooldown=10.0)


def run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# taxonomy / classification
# ---------------------------------------------------------------------------

def test_classify_taxonomy():
    cases = [
        (E.Overloaded(reason="queue_depth"), "overload"),
        (E.DeadlineExceeded(op="divmod"), "deadline"),
        (E.InvalidRequest("bad"), "invalid"),
        (E.OperandRangeError("x[3] out of range"), "invalid"),
        (E.OperandTypeError("x[0]: expected int"), "invalid"),
        (ValueError("whatever"), "invalid"),
        (E.CompileFault(impl="pallas_fused"), "kernel"),
        (E.ExecuteFault(transient=True), "transient"),
        (E.ExecuteFault(transient=False), "kernel"),
        (E.TransferFault(), "transient"),
        (E.PrecomputeFault(), "transient"),
        (E.ServingError("boom"), "fatal"),
        (RuntimeError("RESOURCE_EXHAUSTED: out of memory"), "kernel"),
        (RuntimeError("Mosaic lowering failed"), "kernel"),
        (RuntimeError("UNAVAILABLE: device busy"), "transient"),
        (RuntimeError("segfault adjacent"), "fatal"),
    ]
    for exc, kind in cases:
        assert E.classify(exc) == kind, (exc, kind)
    # legacy except-clause compatibility
    assert isinstance(E.OperandRangeError(""), OverflowError)
    assert isinstance(E.OperandTypeError(""), TypeError)
    assert isinstance(E.InvalidRequest(""), ValueError)
    assert isinstance(E.DeadlineExceeded(""), TimeoutError)


# ---------------------------------------------------------------------------
# fault injector determinism
# ---------------------------------------------------------------------------

def test_injector_skip_times_window_and_heal():
    inj = FaultInjector([FaultSpec(site="execute", op="modmul",
                                   skip=1, times=2)])
    inj.fire("execute", op="modmul")            # skipped
    with pytest.raises(E.ExecuteFault):
        inj.fire("execute", op="modmul")        # 1st armed
    with pytest.raises(E.ExecuteFault):
        inj.fire("execute", op="modmul")        # 2nd armed
    inj.fire("execute", op="modmul")            # healed
    inj.fire("execute", op="reduce")            # label mismatch: never
    st = inj.stats()
    assert st["fired_total"] == 2
    assert st["by_site"]["execute"] == 2
    assert st["specs"][0]["seen"] == 4          # reduce didn't match


def test_injector_rate_is_seeded_deterministic():
    def firing_pattern(seed):
        inj = FaultInjector(
            [FaultSpec(site="execute", rate=0.5, times=0)], seed=seed)
        out = []
        for _ in range(32):
            try:
                inj.fire("execute", op="x")
                out.append(0)
            except E.ExecuteFault:
                out.append(1)
        return out

    a, b = firing_pattern(7), firing_pattern(7)
    assert a == b and 0 < sum(a) < 32
    assert firing_pattern(8) != a               # seed matters


def test_injector_reset_and_kinds():
    inj = FaultInjector([FaultSpec(site="compile", kind="compile"),
                         FaultSpec(site="transfer")])
    with pytest.raises(E.CompileFault):
        inj.fire("compile", op="divmod", impl="pallas_fused")
    with pytest.raises(E.TransferFault):
        inj.fire("transfer", op="divmod")
    inj.fire("compile", op="divmod", impl="pallas_fused")  # exhausted
    inj.reset()
    with pytest.raises(E.CompileFault):
        inj.fire("compile", op="divmod", impl="pallas_fused")
    with pytest.raises(ValueError):
        FaultInjector([FaultSpec(site="nope")])
    with pytest.raises(ValueError):
        FaultInjector([FaultSpec(site="execute", kind="nope")])


# ---------------------------------------------------------------------------
# policy: backoff + breaker + ladder
# ---------------------------------------------------------------------------

def test_backoff_grows_and_caps_deterministically():
    pol = ServingPolicy(backoff_base=0.01, backoff_cap=0.05,
                        backoff_jitter=0.0)
    delays = [backoff_delay(pol, a) for a in range(1, 6)]
    assert delays == [0.01, 0.02, 0.04, 0.05, 0.05]
    rng1, rng2 = random.Random(3), random.Random(3)
    pol = ServingPolicy(backoff_base=0.01, backoff_jitter=0.5)
    assert [backoff_delay(pol, 1, rng1) for _ in range(4)] == \
           [backoff_delay(pol, 1, rng2) for _ in range(4)]


def test_breaker_open_half_open_close_transitions():
    clock = [0.0]
    br = CircuitBreaker(threshold=2, cooldown=10.0,
                        clock=lambda: clock[0])
    assert br.state == "closed" and br.allow()
    br.record_failure()                          # 1/2: still closed
    assert br.state == "closed" and br.allow()
    br.record_failure()                          # 2/2: open
    assert br.state == "open" and not br.allow()
    clock[0] = 9.9
    assert br.state == "open" and not br.allow()
    clock[0] = 10.0                              # cooldown elapsed
    assert br.state == "half_open"
    assert br.allow()                            # the one probe
    assert not br.allow()                        # slot taken
    br.record_success()                          # probe succeeded
    assert br.state == "closed" and br.allow()
    # half-open probe failure re-opens immediately (no threshold)
    br.record_failure()
    br.record_failure()
    clock[0] = 20.0
    assert br.allow()                            # probe
    br.record_failure()
    assert br.state == "open" and not br.allow()
    # a transient fault during the probe releases the slot instead
    clock[0] = 30.0
    assert br.allow() and not br.allow()
    br.release_probe()
    assert br.allow()


def test_kernel_ladder_walks_fallback_chain():
    from repro.kernels import ops as K
    assert K.fallback_chain("pallas_fused") == \
        ["pallas_fused", "pallas_batched", "blocked"]
    assert K.fallback_impl("blocked") is None
    assert K.fallback_impl("scan") is None
    with pytest.raises(ValueError):
        K.fallback_impl("warp_speed")

    clock = [0.0]
    lad = KernelLadder(ServingPolicy(breaker_cooldown=5.0),
                       clock=lambda: clock[0])
    assert lad.select("pallas_fused", 4, 8) == "pallas_fused"
    lad.record_failure("pallas_fused", 4, 8)
    assert lad.select("pallas_fused", 4, 8) == "pallas_batched"
    lad.record_failure("pallas_batched", 4, 8)
    assert lad.select("pallas_fused", 4, 8) == "blocked"
    lad.record_failure("blocked", 4, 8)
    assert lad.select("pallas_fused", 4, 8) is None   # exhausted
    assert lad.quarantined() == ["blocked/b4/m8",
                                 "pallas_batched/b4/m8",
                                 "pallas_fused/b4/m8"]
    # another (bucket, m) is unaffected
    assert lad.select("pallas_fused", 8, 8) == "pallas_fused"
    clock[0] = 5.0                               # probes come back
    assert lad.select("pallas_fused", 4, 8) == "pallas_fused"
    lad.record_success("pallas_fused", 4, 8)
    assert "pallas_fused/b4/m8" not in lad.quarantined()


# ---------------------------------------------------------------------------
# thread-safety: caches under concurrent requests
# ---------------------------------------------------------------------------

def test_concurrent_requests_single_compile_and_precompute():
    rnd = random.Random(11)
    m = 3
    svc = ModArithService(m_limbs=m, e_limbs=1, impl="blocked",
                          batch_buckets=(4,), capture_profiles=False)
    v = rnd.randint(2, B ** m - 1)
    cols = [(
        [rnd.randint(0, B ** m - 1) for _ in range(4)],
        [rnd.randint(0, B ** m - 1) for _ in range(4)],
    ) for _ in range(16)]
    start = threading.Barrier(8)

    def worker(i):
        start.wait()
        a, b = cols[i % len(cols)]
        return svc.modmul(a, b, v)

    with ThreadPoolExecutor(max_workers=8) as pool:
        results = list(pool.map(worker, range(16)))
    for i, res in enumerate(results):
        a, b = cols[i % len(cols)]
        assert res == [(x * y) % v for x, y in zip(a, b)]
    # exactly one Barrett precompute and one bucket compile: the
    # locks forbid double work under racing first touches
    assert svc.ctx_misses == 1
    assert len(svc._ctxs) == 1
    assert svc._fns.misses == 1
    assert svc._fns.hits == 15


def test_concurrent_context_lru_stays_consistent():
    rnd = random.Random(12)
    m = 2
    svc = ModArithService(m_limbs=m, e_limbs=1, impl="blocked",
                          batch_buckets=(2,), max_cached_moduli=3,
                          capture_profiles=False)
    vs = [rnd.randint(2, B ** m - 1) for _ in range(9)]

    with ThreadPoolExecutor(max_workers=6) as pool:
        list(pool.map(svc.context, vs * 4))
    assert len(svc._ctxs) == 3                  # LRU bound held
    assert svc.ctx_misses + svc.ctx_hits == 36
    assert svc.ctx_evictions == svc.ctx_misses - 3


def test_concurrent_calls_over_more_keys_than_slots_stay_exact():
    """More threads than cores share one 3-slot context slab over 9
    keys, each call naming up to 4 of them (so some calls split): one
    call's misses never evict the slot of a context another call is
    using, and every answer stays exact."""
    rnd = random.Random(13)
    m = 2
    svc = ModArithService(m_limbs=m, e_limbs=1, impl="blocked",
                          batch_buckets=(4,), max_cached_moduli=3,
                          capture_profiles=False)
    keys = [rnd.randint(2, B ** m - 1) for _ in range(9)]
    jobs = [([rnd.randint(0, B ** m - 1) for _ in range(4)],
             [rnd.randint(0, B ** m - 1) for _ in range(4)],
             [rnd.choice(keys) for _ in range(4)]) for _ in range(48)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(max_workers=12) as pool:
            futs = [pool.submit(svc.modmul, a, b, vs) for a, b, vs in jobs]
            results = [f.result(timeout=300) for f in futs]
    finally:
        sys.setswitchinterval(switch)
    for (a, b, vs), res in zip(jobs, results):
        assert res == [(x * y) % v for x, y, v in zip(a, b, vs)]
    assert len(svc._ctxs) == 3
    assert svc.ctx_hits + svc.ctx_misses == \
        sum(len(set(vs)) for _, _, vs in jobs)
    assert svc.ctx_evictions == svc.ctx_misses - 3


# ---------------------------------------------------------------------------
# async frontend: retry, deadlines, degradation, overload
# ---------------------------------------------------------------------------

def _modarith(m=3, impl="blocked", **kw):
    kw.setdefault("batch_buckets", (4,))
    kw.setdefault("capture_profiles", False)
    return ModArithService(m_limbs=m, e_limbs=1, impl=impl, **kw)


def test_frontend_retries_transient_faults_with_backoff():
    rnd = random.Random(21)
    svc = _modarith()
    v = rnd.randint(2, B ** 3 - 1)
    a = [rnd.randint(0, B ** 3 - 1) for _ in range(6)]
    b = [rnd.randint(0, B ** 3 - 1) for _ in range(6)]
    inj = FaultInjector([FaultSpec(site="execute", op="modmul",
                                   times=2)])
    pol = ServingPolicy(**FAST)

    async def main():
        async with AsyncFrontend(svc, policy=pol, faults=inj) as fe:
            res = await fe.submit("modmul", a, b, v=v)
            assert res == [(x * y) % v for x, y in zip(a, b)]
            h = fe.healthz()
            assert h["retries"] == 2
            assert h["dropped"] == 0
            assert fe.snapshot()["faults"]["fired_total"] == 2
    run(main())


def test_frontend_transient_exhaustion_raises_terminal_error():
    rnd = random.Random(22)
    svc = _modarith()
    v = rnd.randint(2, B ** 3 - 1)
    inj = FaultInjector([FaultSpec(site="execute", times=0)])  # forever
    pol = ServingPolicy(max_retries=2, backoff_base=0.001,
                        backoff_cap=0.002)

    async def main():
        async with AsyncFrontend(svc, policy=pol, faults=inj) as fe:
            with pytest.raises(E.ExecuteFault):
                await fe.submit("reduce", [5], v=v)
            h = fe.healthz()
            assert h["retries"] == 2 and h["dropped"] == 0
    run(main())


def test_frontend_precompute_fault_is_retried():
    rnd = random.Random(23)
    svc = _modarith()
    v = rnd.randint(2, B ** 3 - 1)
    inj = FaultInjector([FaultSpec(site="precompute", times=1)])
    pol = ServingPolicy(**FAST)

    async def main():
        async with AsyncFrontend(svc, policy=pol, faults=inj) as fe:
            assert await fe.submit("reduce", [B ** 3 + 5], v=v) == \
                [(B ** 3 + 5) % v]
    run(main())
    assert svc.ctx_misses == 1                  # fault fired pre-miss


class _TickingClock(FaultInjector):
    """Fault injector that advances a fake clock by 1.0 at every
    execute site -- makes deadline propagation across chunks exactly
    reproducible (one tick per chunk execution, no real time)."""

    def __init__(self, box):
        super().__init__([])
        self.box = box

    def fire(self, site, **labels):
        if site == "execute":
            self.box[0] += 1.0


def test_frontend_deadline_expires_between_chunks():
    """An 8-row request over 4-row buckets whose deadline passes after
    chunk 1: typed DeadlineExceeded with partial accounting, and the
    not-yet-submitted chunk is cancelled, not executed."""
    rnd = random.Random(24)
    svc = _modarith()
    v = rnd.randint(2, B ** 3 - 1)
    xs = [rnd.randint(0, B ** 6 - 1) for _ in range(8)]
    clock = [0.0]
    inj = _TickingClock(clock)
    pol = ServingPolicy(**FAST)

    async def main():
        async with AsyncFrontend(svc, policy=pol, faults=inj,
                                 clock=lambda: clock[0]) as fe:
            with pytest.raises(E.DeadlineExceeded) as ei:
                await fe.submit("reduce", xs, v=v, timeout=0.5)
            assert ei.value.completed == 4 and ei.value.total == 8
            h = fe.healthz()
            assert h["deadline_exceeded"] == 1 and h["dropped"] == 0
            m = fe.metrics
            assert sum(s.value
                       for s in m.chunks_cancelled.series()) == 1
            # the tier recovers: later traffic is served normally
            clock[0] = 0.0
            assert await fe.submit("reduce", xs[:2], v=v) == \
                [x % v for x in xs[:2]]
    run(main())
    # only chunk 1 ever executed for the expired request (+1 recovery)
    assert svc.telemetry.stats()["rows_true"] == 4 + 2


def test_frontend_already_expired_deadline_never_executes():
    rnd = random.Random(25)
    svc = _modarith()
    v = rnd.randint(2, B ** 3 - 1)

    async def main():
        async with AsyncFrontend(svc, policy=ServingPolicy(**FAST)) as fe:
            with pytest.raises(E.DeadlineExceeded) as ei:
                await fe.submit("reduce", [1, 2, 3], v=v, timeout=0.0)
            assert ei.value.completed == 0 and ei.value.total == 3
    run(main())
    assert svc.telemetry.stats()["rows_true"] == 0


def test_frontend_records_one_queue_wait_per_admitted_request():
    """Every admitted request records exactly one queue wait: at the
    hand-off of its first chunk, or at its resolution when it expired
    before any chunk ran.  Requests refused at admission record none."""
    rnd = random.Random(35)
    svc = _modarith()
    v = rnd.randint(2, B ** 3 - 1)
    xs = [rnd.randint(0, B ** 6 - 1) for _ in range(6)]

    async def main():
        async with AsyncFrontend(svc, policy=ServingPolicy(**FAST)) as fe:
            outs = await asyncio.gather(
                fe.submit("reduce", xs, v=v),           # two chunks
                fe.submit("reduce", xs[:1], v=v),
                fe.submit("reduce", xs[:2], v=v, timeout=0.0),
                fe.submit("nope", [1], v=v),
                return_exceptions=True)
            assert outs[0] == [x % v for x in xs]
            assert outs[1] == [xs[0] % v]
            assert isinstance(outs[2], E.DeadlineExceeded)
            assert isinstance(outs[3], E.InvalidRequest)
            return fe.metrics
    m = run(main())
    waits = m.queue_wait.labels(op="reduce")
    admitted = m.admitted.labels(op="reduce").value
    assert admitted == 3 and waits.count == 3
    assert waits.value >= 0.0
    assert [s.labels for s in m.queue_wait.series()] == [{"op": "reduce"}]


def test_frontend_overload_sheds_typed_rejections():
    rnd = random.Random(26)
    svc = _modarith()
    v = rnd.randint(2, B ** 3 - 1)
    pol = ServingPolicy(max_queue_depth=1, **FAST)

    async def main():
        async with AsyncFrontend(svc, policy=pol) as fe:
            r1, r2 = await asyncio.gather(
                fe.submit("reduce", [7], v=v),
                fe.submit("reduce", [8], v=v),
                return_exceptions=True)
            assert r1 == [7 % v]
            assert isinstance(r2, E.Overloaded)
            assert r2.reason == "queue_depth"
            rej = fe.metrics.rejected.labels(reason="queue_depth")
            assert rej.value == 1
            assert fe.healthz()["dropped"] == 0
    run(main())


def test_frontend_queued_work_estimate_limit():
    rnd = random.Random(27)
    svc = _modarith()
    v = rnd.randint(2, B ** 3 - 1)
    pol = ServingPolicy(max_queued_items=4, **FAST)

    async def main():
        async with AsyncFrontend(svc, policy=pol) as fe:
            big = [rnd.randint(0, B ** 3 - 1) for _ in range(3)]
            r1, r2 = await asyncio.gather(
                fe.submit("reduce", big, v=v),
                fe.submit("reduce", big, v=v),     # 3 + 3 > 4
                return_exceptions=True)
            assert r1 == [x % v for x in big]
            assert isinstance(r2, E.Overloaded)
            assert r2.reason == "queued_work"
    run(main())


@pytest.mark.parametrize("keys", [1, 4])
def test_frontend_coalesces_concurrent_requests_into_one_bucket(keys):
    """Requests over one modulus or over several share bucket chunks:
    each row carries its own modulus into the call."""
    rnd = random.Random(28)
    svc = _modarith()
    vs = [rnd.randint(2, B ** 3 - 1) for _ in range(keys)]
    v = [vs[i % keys] for i in range(4)]
    a = [rnd.randint(0, B ** 3 - 1) for _ in range(4)]
    b = [rnd.randint(0, B ** 3 - 1) for _ in range(4)]

    async def main():
        async with AsyncFrontend(svc,
                                 policy=ServingPolicy(**FAST)) as fe:
            outs = await asyncio.gather(*[
                fe.submit("modmul", [a[i]], [b[i]], v=v[i])
                for i in range(4)])
            assert [o[0] for o in outs] == \
                [(x * y) % w for x, y, w in zip(a, b, v)]
    run(main())
    st = svc.telemetry.stats()
    # 4 single-row requests coalesced into at most 2 padded buckets
    # (first arrival may start a cycle alone) -- NOT one per request
    # or per modulus
    assert st["rows_padded"] <= 8, st
    assert svc.ctx_misses == keys


def test_frontend_hands_a_service_without_a_slab_one_modulus_a_call():
    """A stand-in whose `stats()` reports no context slab (as one that
    serves a reference one modulus at a time) gets each chunk's
    modulus as one int: its requests group by (op, modulus)."""
    rnd = random.Random(38)
    real = _modarith()

    class OneModulus:
        def __init__(self):
            self.seen = []

        def __getattr__(self, name):
            return getattr(real, name)

        def stats(self):
            return {"ctx_cache": {"size": 0, "hits": 0, "misses": 0}}

        def modmul(self, a, b, v, *, impl=None):
            self.seen.append(v)
            return real.modmul(a, b, v, impl=impl)

    svc = OneModulus()
    vs = [rnd.randint(2, B ** 3 - 1) for _ in range(2)]
    a = [rnd.randint(0, B ** 3 - 1) for _ in range(4)]

    async def main():
        async with AsyncFrontend(svc, policy=ServingPolicy(**FAST)) as fe:
            return await asyncio.gather(*[
                fe.submit("modmul", [a[i]], [a[i]], v=vs[i % 2])
                for i in range(4)])
    outs = run(main())
    assert [o[0] for o in outs] == [x * x % vs[i % 2]
                                    for i, x in enumerate(a)]
    assert all(isinstance(v, int) for v in svc.seen)
    assert len(svc.seen) >= 2


class _Probe(FaultInjector):
    """Fault injector that records every site it is asked about (and
    can advance a fake clock at each execute)."""

    def __init__(self, specs=(), clock=None):
        super().__init__(list(specs))
        self.clock = clock
        self.sites = []

    def fire(self, site, **labels):
        self.sites.append(site)
        if site == "execute" and self.clock is not None:
            self.clock[0] += 1.0
        super().fire(site, **labels)


@pytest.mark.parametrize("case", ["split", "retry", "degrade", "deadline"])
def test_frontend_coalesces_across_moduli_keeping_rows_aligned(case):
    """Six requests over four moduli coalesce into one group whose 10
    rows `Batcher.plan` splits into bucket-4 chunks (4, 4, 2), each
    carrying its rows' moduli.  A transient retry, a kernel fault that
    degrades the chunk down the ladder, and a deadline that expires one
    request between chunks each leave every other row answered by its
    own modulus."""
    rnd = random.Random(37)
    m = 3
    impl = "pallas_batched" if case == "degrade" else "blocked"
    svc = _modarith(m=m, impl=impl)
    keys = [rnd.randint(2, B ** m - 1) for _ in range(4)]
    sizes = [3, 2, 1, 2, 1, 1]
    mods = [keys[0], keys[1], keys[0], keys[2], keys[3], keys[1]]
    reqs = [([rnd.randint(0, B ** m - 1) for _ in range(n)],
             [rnd.randint(0, B ** m - 1) for _ in range(n)], v)
            for n, v in zip(sizes, mods)]
    clock = [0.0]
    specs = {"retry": [FaultSpec(site="execute", op="modmul", times=2)],
             "degrade": [FaultSpec(site="execute", impl="pallas_batched",
                                   kind="kernel", times=0)]}
    inj = _Probe(specs.get(case, ()),
                 clock if case == "deadline" else None)
    # request 1 (rows 3-4) spans chunks 1 and 2
    timeouts = [None, 0.5 if case == "deadline" else None] + [None] * 4

    async def main():
        async with AsyncFrontend(svc, policy=ServingPolicy(**FAST),
                                 faults=inj, clock=lambda: clock[0]) as fe:
            outs = await asyncio.gather(*[
                fe.submit("modmul", a, b, v=v, timeout=t)
                for (a, b, v), t in zip(reqs, timeouts)],
                return_exceptions=True)
            return outs, fe.healthz(), fe.metrics
    outs, health, metrics = run(main())
    for k, ((a, b, v), out) in enumerate(zip(reqs, outs)):
        if case == "deadline" and k == 1:      # expired after chunk 1
            assert isinstance(out, E.DeadlineExceeded)
            assert (out.completed, out.total) == (1, 2)
            continue
        assert out == [(x * y) % v for x, y in zip(a, b)], (case, k)
    assert health["dropped"] == 0
    # three chunk executions, and one more per failed attempt (the
    # breaker quarantines pallas_batched at its first kernel fault)
    extra = {"retry": 2, "degrade": 1}.get(case, 0)
    assert inj.sites.count("execute") == 3 + extra
    assert svc.telemetry.stats()["rows_true"] == 10 + 4 * extra
    if case == "degrade":
        assert svc.kernel_plans[4].impl == "blocked"
        assert health["quarantine"] == ["pallas_batched/b4/m3"]
    assert sum(s.value for s in metrics.retries.series()) == \
        (2 if case == "retry" else 0)


@pytest.mark.parametrize("op", ["divmod", "modexp"])
def test_frontend_exact_on_full_and_padded_buckets(op):
    """Rows that fill bucket 4, rows that pad it, and a request split
    into a full chunk and a padded one come back as Python's
    divmod / pow, edge operands (0, 1, B^m - 1) included."""
    rnd = random.Random(36)
    m, top = 4, B ** 4 - 1
    xs = [0, 1, top, top - 1] + [rnd.randint(0, top) for _ in range(9)]
    if op == "divmod":
        svc = BigintDivisionService(m_limbs=m, impl="blocked",
                                    batch_buckets=(4,),
                                    capture_profiles=False)
        ys = [1, top, 2, B] + [rnd.randint(1, B ** rnd.randint(1, m) - 1)
                               for _ in range(9)]
        want = [(x // y, x % y) for x, y in zip(xs, ys)]
        cols, kw = (xs, ys), {}
    else:
        svc = ModArithService(m_limbs=m, e_limbs=2, impl="blocked",
                              batch_buckets=(4,), capture_profiles=False)
        n = rnd.randint(B ** (m - 1), top) | 1
        xs = [x % n for x in xs]
        es = [0, 1, B ** 2 - 1, 2] + [rnd.randint(0, B ** 2 - 1)
                                      for _ in range(9)]
        want = [pow(x, e, n) for x, e in zip(xs, es)]
        cols, kw = (xs, es), {"v": n}

    def answers(out):
        return list(zip(*out)) if op == "divmod" else out

    async def main():
        async with AsyncFrontend(svc, policy=ServingPolicy(**FAST)) as fe:
            for lo, hi in [(0, 4), (4, 7), (7, 13)]:   # full, pad, both
                got = await fe.submit(op, *(c[lo:hi] for c in cols), **kw)
                assert answers(got) == want[lo:hi], (lo, hi)
    run(main())
    st = svc.telemetry.stats()
    assert st["rows_true"] == 13 and st["rows_padded"] == 16, st


def test_frontend_stop_without_drain_cancels_queued():
    rnd = random.Random(29)
    svc = _modarith()
    v = rnd.randint(2, B ** 3 - 1)

    async def main():
        fe = AsyncFrontend(svc, policy=ServingPolicy(**FAST))
        await fe.start()
        await fe.stop(drain=False)
        with pytest.raises(E.Overloaded):
            await fe.submit("reduce", [1], v=v)
        assert fe.healthz()["status"] == "stopped"
        assert not fe.ready()
    run(main())


# ---------------------------------------------------------------------------
# kernel degradation ladder (the chaos centerpiece)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_frontend_degrades_on_compile_fault_bit_identical():
    """A Pallas compile fault on the requested impl must quarantine
    (impl, bucket, precision) and fall down the registry ladder --
    with results bit-identical to the no-fault sync path, the
    downgrade recorded in KernelPlan + snapshot, and nothing
    dropped."""
    rnd = random.Random(31)
    m = 4
    svc = BigintDivisionService(m_limbs=m, impl="pallas_fused",
                                batch_buckets=(4,),
                                capture_profiles=False)
    us = [rnd.randint(0, B ** m - 1) for _ in range(6)]
    vs = [rnd.randint(1, B ** m - 1) for _ in range(6)]
    inj = FaultInjector([FaultSpec(site="compile", impl="pallas_fused",
                                   kind="compile", times=0)])
    pol = ServingPolicy(**FAST)

    async def main():
        async with AsyncFrontend(svc, policy=pol, faults=inj) as fe:
            qs, rs = await fe.submit("divmod", us, vs)
            assert qs == [u // v for u, v in zip(us, vs)]
            assert rs == [u % v for u, v in zip(us, vs)]
            snap = fe.snapshot()
            health = snap["frontend"]["health"]
            assert health["status"] == "degraded"
            assert health["quarantine"] == ["pallas_fused/b4/m4"]
            assert health["dropped"] == 0
            plan = svc.kernel_plans[4]
            assert plan.impl == "pallas_batched"
            assert plan.degraded_from == "pallas_fused"
            deg = fe.metrics.degraded.labels(
                from_impl="pallas_fused", to_impl="pallas_batched")
            assert deg.value >= 1
    run(main())


@pytest.mark.slow
def test_frontend_half_open_probe_restores_healed_kernel():
    """After the breaker cooldown, ONE probe request retries the
    quarantined impl; a healed kernel (fault plan exhausted) closes
    the breaker and traffic returns to the fast path."""
    rnd = random.Random(32)
    m = 2
    svc = BigintDivisionService(m_limbs=m, impl="pallas_fused",
                                batch_buckets=(2,),
                                capture_profiles=False)
    inj = FaultInjector([FaultSpec(site="compile", impl="pallas_fused",
                                   kind="compile", times=1)])
    clock = [0.0]
    pol = ServingPolicy(**FAST)

    async def main():
        async with AsyncFrontend(svc, policy=pol, faults=inj,
                                 clock=lambda: clock[0]) as fe:
            us = [rnd.randint(0, B ** m - 1) for _ in range(2)]
            vs = [rnd.randint(1, B ** m - 1) for _ in range(2)]
            await fe.submit("divmod", us, vs)
            assert fe.healthz()["quarantine"] == ["pallas_fused/b2/m2"]
            assert svc.kernel_plans[2].degraded_from == "pallas_fused"
            clock[0] = pol.breaker_cooldown + 1.0   # probation over
            qs, rs = await fe.submit("divmod", us, vs)
            assert qs == [u // v for u, v in zip(us, vs)]
            assert fe.healthz()["quarantine"] == []
            assert fe.healthz()["status"] == "ok"
            assert svc.kernel_plans[2].impl == "pallas_fused"
            assert svc.kernel_plans[2].degraded_from == ""
    run(main())


def test_frontend_ladder_exhaustion_is_a_typed_terminal_error():
    rnd = random.Random(33)
    svc = _modarith(impl="blocked")              # terminal impl
    v = rnd.randint(2, B ** 3 - 1)
    inj = FaultInjector([FaultSpec(site="execute", kind="kernel",
                                   times=0)])
    pol = ServingPolicy(**FAST)

    async def main():
        async with AsyncFrontend(svc, policy=pol, faults=inj) as fe:
            with pytest.raises(E.ExecuteFault):
                await fe.submit("reduce", [9], v=v)
            h = fe.healthz()
            assert h["dropped"] == 0
            assert "blocked/b4/m3" in h["quarantine"]
    run(main())


def test_frontend_metrics_export_is_merged_and_parseable():
    rnd = random.Random(34)
    svc = _modarith()
    v = rnd.randint(2, B ** 3 - 1)

    async def main():
        async with AsyncFrontend(svc,
                                 policy=ServingPolicy(**FAST)) as fe:
            await fe.submit("reduce", [1, 2], v=v)
            lines = fe.metrics_lines()
            names = {ln.split("{")[0].split(" ")[0] for ln in lines}
            # frontend queue/failure families + service families in
            # one export
            assert "queue_depth" in names
            assert "admitted_total" in names
            assert any(n.startswith("request_seconds") for n in names)
            assert any(n.startswith("requests_total") for n in names)
            for ln in lines:                     # "name... value"
                float(ln.rsplit(" ", 1)[1])
    run(main())


def test_frontend_validation_rejects_before_admission():
    svc = _modarith()

    async def main():
        async with AsyncFrontend(svc,
                                 policy=ServingPolicy(**FAST)) as fe:
            with pytest.raises(E.InvalidRequest):
                await fe.submit("nope", [1], v=5)
            with pytest.raises(E.OperandTypeError):
                await fe.submit("reduce", [1.5], v=5)
            with pytest.raises(E.InvalidRequest):
                await fe.submit("modmul", [1], [2, 3], v=5)
            with pytest.raises(E.InvalidRequest):
                await fe.submit("reduce", [1])   # missing modulus
            assert await fe.submit("reduce", [], v=5) == []
            rej = fe.metrics.rejected.labels(reason="invalid")
            assert rej.value == 4                # empty is not invalid
            assert fe.healthz()["queue_depth"] == 0
    run(main())
