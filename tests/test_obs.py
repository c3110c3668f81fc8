"""Observability layer: telemetry registry, cost model, service
counters and the measured-vs-model snapshot contract.

Everything here is structural -- exact counter values for scripted
request sequences, trace-time launch counts -- so nothing depends on
wall-clock timing.
"""

import json
import random

import pytest

import jax
import jax.numpy as jnp

from repro.core import bigint as bi
from repro.core import shinv as S
from repro.obs import costmodel as CM
from repro.obs import report as RPT
from repro.obs import telemetry as T
from repro.serving.bigint_service import BigintDivisionService
from repro.serving.modexp_service import ModArithService

B = bi.BASE


# ---------------------------------------------------------------------------
# telemetry registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = T.Registry()
    c = reg.counter("reqs", "requests", ("op",))
    c.labels(op="div").inc()
    c.labels(op="div").inc(2)
    c.labels(op="mul").inc(5)
    assert [(s.labels, s.value) for s in c.series()] == \
        [({"op": "div"}, 3.0), ({"op": "mul"}, 5.0)]
    with pytest.raises(ValueError):
        c.labels(op="div").inc(-1)          # counters only go up

    g = reg.gauge("depth")
    g.set(4)
    g.dec()
    assert g._default().value == 3.0

    h = reg.histogram("lat", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0, 0.7):
        h.observe(v)
    s = h._default()
    assert s.count == 4 and s.counts == [2, 1, 1]
    assert s.value == pytest.approx(56.2)


def test_registry_idempotent_declare_and_mismatch():
    reg = T.Registry()
    a = reg.counter("x", "first", ("k",))
    assert reg.counter("x", "again", ("k",)) is a
    with pytest.raises(ValueError):
        reg.gauge("x")                      # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("x", labelnames=("other",))   # label mismatch
    with pytest.raises(ValueError):
        a.labels(wrong="v")                 # undeclared label name


def test_registry_export_shapes():
    reg = T.Registry()
    reg.counter("n", "things", ("op",)).labels(op="a").inc(2)
    reg.histogram("t", buckets=(1.0,)).observe(0.5)
    dump = json.loads(reg.to_json())
    assert [f["name"] for f in dump] == ["n", "t"]
    lines = reg.to_lines()
    assert "n{op=a} 2" in lines
    assert "t_bucket{le=1.0} 1" in lines and "t_count 1" in lines


def test_registry_rejects_tracers():
    reg = T.Registry()
    c = reg.counter("n")

    @jax.jit
    def bad(x):
        c.inc(x)                            # recording a tracer is a bug
        return x

    with pytest.raises(Exception):
        bad(jnp.float32(1.0))


def _host_events(trace_dir) -> dict:
    """{name: [(start_ns, end_ns)]} of the host events of the one
    profiler trace written under `trace_dir`."""
    import glob
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    out.setdefault(ev.name, []).append(
                        (s, s + int(ev.duration_ns)))
    return out


def test_span_observes_its_series_and_is_traced_by_name(tmp_path):
    reg = T.Registry()
    h = reg.histogram("lat", labelnames=("phase",))
    other = reg.histogram("other")
    with T.span("untraced", h.labels(phase="a")):
        pass                                  # no profiler session
    jax.profiler.start_trace(str(tmp_path))
    try:
        with T.span("test.inner", (h.labels(phase="b"), other._default())):
            pass
        outer = T.span("test.outer", h.labels(phase="c"))
        late = T.span("test.late")
        outer.end()                           # ends before `late`
        outer.end()                           # only the first end counts
        late.end()
    finally:
        jax.profiler.stop_trace()
    counts = {s.labels["phase"]: s.count for s in h.series()}
    assert counts == {"a": 1, "b": 1, "c": 1}
    assert other._default().count == 1
    assert all(s.value >= 0.0 for s in h.series())
    ev = _host_events(tmp_path)
    assert len(ev["test.inner"]) == len(ev["test.outer"]) == 1
    (o0, o1), = ev["test.outer"]
    (l0, l1), = ev["test.late"]
    assert o0 <= l0 <= o1 <= l1               # overlapping, out of order
    assert "untraced" not in ev


# ---------------------------------------------------------------------------
# cost model consistency
# ---------------------------------------------------------------------------

def test_fused_reexports_are_the_costmodel_constants():
    from repro.kernels import fused as F
    assert F.FUSED_STEP_LAUNCHES is CM.FUSED_STEP_LAUNCHES
    assert F.FUSED_CORRECT_LAUNCHES is CM.FUSED_CORRECT_LAUNCHES
    assert F.FUSED_BARRETT_LAUNCHES is CM.FUSED_BARRETT_LAUNCHES
    assert F.UNFUSED_STEP_GLUE_OPS is CM.UNFUSED_STEP_GLUE_OPS


def test_divmod_launch_predictions():
    for m in (4, 16, 256, 2048):
        it = S.refine_iters(m)
        assert CM.refine_iters(m) == it
        assert CM.divmod_launches(m, "pallas_fused") == 2 * it + 1
        assert CM.divmod_launches(m, "pallas_batched") == 2 * it + 2
        assert CM.divmod_launches(m, "blocked") == 0


def test_refine_window_matches_refine_schedule():
    # the model mirror of core/shinv.py:_refine's static window
    for width in (32, 80, 600):
        for i in range(12):
            assert CM.refine_window(i, width) == \
                min(max(32, 2 ** (i + 1) + 16), width)
            assert CM.refine_window(i, width, windowed=False) == width
    # windowed work is a bounded geometric series, unfused is linear
    assert CM.refine_mul_work(256, windowed=True) < \
        CM.refine_mul_work(256, windowed=False)


def test_modexp_ladder_counts():
    lad = CM.modexp_ladder(16, 4)
    assert lad["n_windows"] == 4
    assert lad["modmuls"] == 16 + 16 + 4        # sq + table + window
    assert lad["reductions"] == lad["modmuls"] + 2
    with pytest.raises(ValueError):
        CM.modexp_ladder(10, 4)                 # window must divide
    assert CM.modexp_launches(16, 4, "pallas_fused") == \
        lad["modmuls"] * CM.modmul_launches("pallas_fused") + 2
    assert CM.model_launches("modexp", 8, "pallas_fused") is None


# ---------------------------------------------------------------------------
# service runtime counters (exact, scripted sequences)
# ---------------------------------------------------------------------------

def test_division_service_pad_waste_exact():
    rnd = random.Random(3)
    m = 4
    svc = BigintDivisionService(m_limbs=m, impl="blocked",
                                batch_buckets=(4,),
                                capture_profiles=False)
    us = [rnd.randint(0, B ** m - 1) for _ in range(6)]
    vs = [rnd.randint(1, B ** m - 1) for _ in range(6)]
    qs, rs = svc.divide(us, vs)             # chunks: (0,4,4), (4,6,4)
    assert all((q, r) == divmod(u, v)
               for u, v, q, r in zip(us, vs, qs, rs))
    st = svc.stats()
    assert st["requests"] == {"divmod": 1}
    assert st["items"] == {"divmod": 6}
    assert st["rows_true"] == 6 and st["rows_padded"] == 8
    assert st["pad_waste"] == pytest.approx((8 - 6) / 8)
    assert st["bucket_compiles"] == 1 and st["bucket_reuses"] == 1
    lat = st["bucket_seconds"]["divmod/b4"]
    assert lat["count"] == 2 and lat["sum"] >= 0.0

    svc.divide(us[:4], vs[:4])              # exact bucket: no padding
    st = svc.stats()
    assert st["rows_true"] == 10 and st["rows_padded"] == 12
    assert st["pad_waste"] == pytest.approx(2 / 12)


def _phase_counts(svc) -> dict:
    phases = svc.telemetry.registry.get("phase_seconds")
    return {f"{s.labels['op']}/{s.labels['phase']}": s.count
            for s in phases.series()}


def test_division_service_records_each_phase_once_per_chunk():
    m = 4
    svc = BigintDivisionService(m_limbs=m, impl="blocked",
                                batch_buckets=(2, 4))
    us = [B ** m - 1 - i for i in range(7)]
    vs = [3 + i for i in range(7)]
    qs, rs = svc.divide(us, vs)
    assert list(zip(qs, rs)) == [divmod(u, v) for u, v in zip(us, vs)]
    st = svc.stats()
    counts = _phase_counts(svc)
    chunks = 2                                # (0, 4, 4), (4, 7, 4)
    assert counts == {"divmod/validate": 1, "divmod/profile": 1,
                      "divmod/pack": chunks, "divmod/execute": chunks,
                      "divmod/unpack": chunks}
    assert sum(h["count"] for h in st["bucket_seconds"].values()) == \
        counts["divmod/execute"]
    execute, = [s for s in svc.telemetry.registry.get(
        "phase_seconds").series() if s.labels["phase"] == "execute"]
    assert st["bucket_seconds"]["divmod/b4"]["sum"] == \
        pytest.approx(execute.value)             # one timing, two series


def test_modexp_service_records_each_phase_once_per_chunk():
    rnd = random.Random(5)
    m = 4
    svc = ModArithService(m_limbs=m, e_limbs=1, impl="blocked",
                          batch_buckets=(2,), capture_profiles=False)
    v = rnd.randint(2, B ** m - 1)
    a = [rnd.randint(0, B ** m - 1) for _ in range(3)]
    e = [rnd.randint(0, B - 1) for _ in range(3)]
    assert svc.modexp(a, e, v) == [pow(x, y, v) for x, y in zip(a, e)]
    assert svc.modexp(a[:1], e[:1], v) == [pow(a[0], e[0], v)]
    counts = _phase_counts(svc)
    chunks = 2 + 1                            # (0, 2), (2, 3); then (0, 1)
    # each chunk looks up its contexts; the one cold key is computed
    # inside the first chunk's lookup
    assert counts == {"modexp/validate": 2, "modexp/contexts": chunks,
                      "modexp/precompute": 1,
                      "modexp/pack": chunks, "modexp/execute": chunks,
                      "modexp/unpack": chunks}
    st = svc.stats()
    assert st["bucket_seconds"]["modexp/b2"]["count"] == chunks
    reg = svc.telemetry.registry
    calls, = reg.get("call_moduli").series()
    assert (calls.count, calls.value) == (chunks, chunks)
    fills, = reg.get("ctx_precompute_rows").series()
    assert (fills.count, fills.value) == (1, 1)


def test_modarith_ctx_cache_counters_exact():
    rnd = random.Random(9)
    m = 4
    svc = ModArithService(m_limbs=m, e_limbs=1, impl="blocked",
                          batch_buckets=(2,), max_cached_moduli=2,
                          capture_profiles=False)
    vs = [rnd.randint(2, B ** m - 1) for _ in range(3)]
    x = [rnd.randint(0, B ** (2 * m) - 1)]
    # miss, miss, hit, miss (-> evicts vs[1]... no: vs[0] is LRU), hit
    svc.reduce(x, vs[0])
    svc.reduce(x, vs[1])
    svc.reduce(x, vs[1])
    svc.reduce(x, vs[2])                    # evicts vs[0] (LRU)
    svc.reduce(x, vs[2])
    st = svc.stats()["ctx_cache"]
    assert st == {"hits": 2, "misses": 3, "evictions": 1, "size": 2,
                  "slots": 2, "hit_rate": pytest.approx(2 / 5)}
    # the labeled counter series carries the same events
    ctx = svc.telemetry.registry.get("ctx_cache_total")
    by_event = {s.labels["event"]: s.value for s in ctx.series()}
    assert by_event == {"hit": 2.0, "miss": 3.0, "eviction": 1.0}
    # vs[0] was evicted: touching it again is a miss
    svc.reduce(x, vs[0])
    assert svc.stats()["ctx_cache"]["misses"] == 4


# ---------------------------------------------------------------------------
# snapshots and measured-vs-model
# ---------------------------------------------------------------------------

def test_snapshot_structure_blocked():
    svc = BigintDivisionService(m_limbs=4, impl="blocked",
                                batch_buckets=(2,))
    svc.divide([7], [3])
    snap = svc.snapshot()
    assert snap["service"] == "bigint_division"
    assert snap["impl"] == "blocked"
    assert snap["iters"] == S.refine_iters(4)
    entry = snap["buckets"][2]
    assert entry["plan"]["impl"] == "blocked"
    prof = entry["static"]["divmod"]
    assert set(prof) == {"pallas_launches", "runtime_pallas_launches",
                         "xla_eqns", "total_eqns"}
    assert prof["pallas_launches"] == 0     # blocked = pure XLA
    rows = RPT.measured_vs_model(snap)
    assert len(rows) == 1 and rows[0]["match"]
    assert rows[0]["model_launches"] == 0
    assert "measured vs cost model" in RPT.render_measured_vs_model(snap)


def test_measured_vs_model_fused_smoke():
    # trace-only: profile_bucket compiles nothing and executes nothing
    m, bucket = 16, 4
    svc = BigintDivisionService(m_limbs=m, impl="pallas_fused",
                                batch_buckets=(bucket,))
    prof = svc.profile_bucket(bucket)
    want = 2 * S.refine_iters(m) + 1
    assert prof["divmod"]["pallas_launches"] == want
    rows = RPT.measured_vs_model(svc.snapshot())
    assert rows == [r for r in rows if r["match"]]
    assert rows[0]["measured_launches"] == rows[0]["model_launches"] == want


def test_modarith_snapshot_measured_vs_model():
    m, bucket = 8, 2
    svc = ModArithService(m_limbs=m, e_limbs=1, impl="pallas_fused",
                          batch_buckets=(bucket,))
    svc.profile_bucket("reduce", bucket)
    svc.profile_bucket("modmul", bucket)
    snap = svc.snapshot()
    assert snap["service"] == "modarith"
    by_op = {r["op"]: r for r in RPT.measured_vs_model(snap)}
    assert by_op["reduce"]["measured_launches"] == \
        CM.barrett_launches("pallas_fused") == 1
    assert by_op["modmul"]["measured_launches"] == \
        CM.modmul_launches("pallas_fused") == 2
    assert all(r["match"] for r in by_op.values())


@pytest.mark.slow
def test_acceptance_fused_launches_2e12_to_2e15_bits():
    """The PR acceptance sweep: measured launches == 2*iters + 1 on
    2^12..2^15-bit operands (trace-only, CPU interpret mode)."""
    for lb in (12, 13, 14, 15):
        m = bi.width_for_bits(1 << lb)
        svc = BigintDivisionService(m_limbs=m, impl="pallas_fused",
                                    batch_buckets=(2,))
        prof = svc.profile_bucket(2)
        want = 2 * S.refine_iters(m) + 1
        assert prof["divmod"]["pallas_launches"] == want, (lb, prof)
        rows = RPT.measured_vs_model(svc.snapshot())
        assert rows[0]["match"] and rows[0]["measured_launches"] == want


# ---------------------------------------------------------------------------
# report: shared BENCH schema
# ---------------------------------------------------------------------------

def test_merge_json_field_wise(tmp_path):
    p = str(tmp_path / "BENCH_x.json")
    RPT.merge_json(p, [{"bits": 256, "batch": 4, "impl": "a", "ms": 1.0}])
    # a structural-only refresh must not clobber the measured timing
    RPT.merge_json(p, [{"bits": 256, "batch": 4, "impl": "a",
                        "launches": 13},
                       {"bits": 512, "batch": 4, "impl": "a",
                        "ms": 2.0}])
    rows = json.load(open(p))
    assert rows == [
        {"bits": 256, "batch": 4, "impl": "a", "ms": 1.0, "launches": 13},
        {"bits": 512, "batch": 4, "impl": "a", "ms": 2.0}]


def test_render_table_none_and_floats():
    out = RPT.render_table([{"a": 1, "b": None}, {"a": 2.5, "b": "x"}],
                           title="t")
    assert out.splitlines()[0] == "t"
    assert "-" in out and "2.50" in out
