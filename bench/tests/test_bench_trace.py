"""The reduction from a trace to busy time, idle share, kernel time and
count, and the breakdown, on traces built by hand; and the harness's
span and record around the endpoint each service call goes through."""

import contextlib
import sys
from pathlib import Path
from types import SimpleNamespace as NS

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import tracereduce as T  # noqa: E402

MS = 1_000_000


def reading():
    # two calls of 4 rows, one program run each; kernels k overlap glue
    # g in the first; a stray program runs outside the calls
    ops = [("k1", 0 * MS, 4 * MS, True), ("g1", 3 * MS, 6 * MS, False),
           ("k2", 10 * MS, 12 * MS, True), ("g2", 12 * MS, 13 * MS, False),
           ("stray", 20 * MS, 21 * MS, False)]
    host = [("bench.call rows=4", -1 * MS, 7 * MS),
            ("bench.call rows=4", 9 * MS, 14 * MS),
            ("convert", 6 * MS, 9 * MS), ("loop", 0, 25 * MS)]
    return T.Reading(ops=ops, host=host,
                     programs=[(0, 6 * MS), (10 * MS, 13 * MS),
                               (20 * MS, 21 * MS)],
                     spans=[(-1 * MS, 7 * MS, 4), (9 * MS, 14 * MS, 4)])


def test_union_of_overlapping_intervals():
    assert T.union([(0, 4), (3, 6), (10, 12), (12, 13)]) == 9
    assert T.union([(5, 6), (0, 10)]) == 10
    assert T.union([]) == 0
    # 6 + 3 ms inside the calls; the stray op outside them is left out
    assert reading().busy_s() == 0.009
    assert reading().window_s() == 0.015          # -1 .. 14 ms


def test_kernel_time_and_count_inside_the_call_spans():
    r = reading()
    assert r.kernel_count() == 2
    assert abs(r.kernel_time_s() - 0.006) < 1e-12
    assert r.span_rows() == 8


def test_ops_are_placed_by_their_program_run_across_the_clocks():
    # the device's clock reads 0.4 ms early against the host's, as on a
    # v5e: the first call's program begins before its span does, and the
    # program of a call whose span is not whole overlaps the last span
    us = MS // 1000
    ops = [("k_small", 600 * us, 900 * us, True),
           ("g", 900 * us, 1000 * us, False),
           ("k_big", 1000 * us, 10600 * us, True),
           ("k_next", 14800 * us, 16000 * us, True)]
    r = T.Reading(ops=ops,
                  programs=[(-9000 * us, 700 * us), (600 * us, 10600 * us),
                            (14800 * us, 24800 * us)],
                  spans=[(1000 * us, 15000 * us, 1)])
    assert r.calls_programs() == [(600 * us, 10600 * us)]
    assert r.kernel_count() == 2
    assert abs(r.busy_s() - 0.010) < 1e-12


def test_breakdown_orders_ops_and_gaps_and_labels_gaps():
    b = reading().breakdown()
    assert [n for n, _ in b["device_ops"]] == ["k1", "g1", "k2", "g2"]
    # gaps inside the window -1 .. 14 ms: 6..10, 13..14 and -1..0
    assert [round(s, 6) for _, s in b["idle_gaps"]] == [0.004, 0.001,
                                                        0.001]
    # gap 6..10 ms: "convert" (6..9) covers its middle 8 ms and is
    # shorter than the loop; the others lie in a call's span
    assert [n for n, _ in b["idle_gaps"]] == ["convert",
                                              "bench.call rows=4",
                                              "bench.call rows=4"]


def test_self_times_count_nested_ops_once():
    ops = [("while", 0, 100, False), ("k", 10, 30, True),
           ("fusion", 30, 35, False), ("k", 50, 70, True),
           ("after", 100, 110, False)]
    assert T.self_times(ops) == {"while": 55, "k": 40, "fusion": 5,
                                 "after": 10}


def test_readers_on_a_hand_built_trace():
    r = reading()
    cfg = harness.Cell.load("div2p15-batch").cfg
    op = harness.load_module(BENCH / "ops" / "divmod.py")
    run = harness.Run(cfg=cfg, op=op, window_s=30.0, trace=r,
                      peaks={"int8_ops_per_s": 393e12,
                             "hbm_bytes_per_s": 819e9})

    def read(name):
        return harness.load_module(BENCH / "metrics" / f"{name}.py").read(run)
    assert abs(read("device.idle_share") - 40.0) < 1e-9
    assert abs(read("kernel.pallas_share") - 100 * 6 / 9) < 1e-9
    assert read("core.launches_per_call") == 1.0
    ops = 8 * op.ops_per_row(cfg)
    assert abs(read("kernel.pallas_roofline")
               - 100 * ops / 393e12 / 0.006) < 1e-9
    # the traced calls span -1 .. 14 ms
    assert abs(read("step_mfu") - 100 * ops / 393e12 / 0.015) < 1e-9
    # nothing to read: the readers return nothing, never 0
    empty = harness.Run(cfg=cfg, op=op, window_s=30.0)
    for name in ("device.idle_share", "kernel.pallas_share",
                 "core.launches_per_call", "kernel.pallas_roofline",
                 "step_mfu", "ctx.miss_share", "service.rows_per_call",
                 "frontend.requests_per_cycle", "service.call_share"):
        assert harness.load_module(
            BENCH / "metrics" / f"{name}.py").read(empty) is None, name


def test_from_profile_sorts_planes_and_finds_kernels():
    # op events as a v5e trace names them: by their HLO text
    glue = ("%fusion.6 = s32[128,2056]{1,0:T(8,128)S(1)} fusion(s32[128,"
            "4224]{1,0:T(8,128)S(1)} %bitcast.161), kind=kLoop")
    kern = ("%vmap__.44 = s32[8,16,2176]{2,1,0:T(8,128)S(1)} custom-call("
            "s32[8,16,2176]{2,1,0:T(8,128)S(1)} %bitcast.119), "
            'custom_call_target="tpu_custom_call", operand_layout_'
            "constraints={s32[8,16,2176]{2,1,0}}")

    def ev(name, start, dur, **stats):
        return NS(name=name, start_ns=start, duration_ns=dur,
                  stats=list(stats.items()))
    pd = NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_f", 0, 100)]),
            NS(name="XLA Ops", events=[
                ev(glue, 0, 10, device_duration_ps="10000"),
                ev(kern, 10, 50), ev(kern, 70, 5)]),
            NS(name="Async XLA Ops", events=[ev("%copy-start = ...", 0, 9)])]),
        NS(name="/host:CPU", lines=[
            NS(name="python", events=[ev("bench.call rows=3", -5, 120),
                                      ev("other", 0, 1)])])])
    r = T.from_profile(pd)
    assert r.ops == [("fusion.6", 0, 10, False),
                     ("vmap__.44", 10, 60, True),
                     ("vmap__.44", 70, 75, True)]
    assert r.spans == [(-5, 115, 3)]
    assert r.programs == [(0, 100)]
    assert len(r.host) == 2
    assert r.kernel_count() == 2
    assert r.window_s() == 120e-9


def test_tracer_counts_the_calls_that_lie_whole_in_the_trace():
    calls = [(0.0, 0.5, 8), (0.6, 1.1, 8), (1.2, 1.7, 8), (1.8, 2.3, 8)]
    tr = harness.Tracer(None, calls)
    assert tr.whole_calls(0.55, 2.0) == 2        # (0.6, 1.1), (1.2, 1.7)
    assert tr.whole_calls(0.55, 1.0) == 0        # the first is still open
    assert tr.whole_calls(2.5, 3.0) == 0
    calls.append((2.6, 2.9, 8))                  # appended while tracing
    assert tr.whole_calls(2.5, 3.0) == 1


def test_spanned_records_and_spans_the_named_endpoint(monkeypatch):
    import jax
    names = []

    @contextlib.contextmanager
    def annotation(name):
        names.append(name)
        yield

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)

    class Service:
        m_limbs = 4

        def reduce(self, xs, v, *, impl=None):
            return [x % v for x in xs]

        def modexp(self, a, e, v, *, impl=None):
            return [pow(x, y, v) for x, y in zip(a, e)]

    svc = Service()
    s = harness.Spanned(svc, "reduce")
    assert s.reduce([10, 11, 12], 7, impl="blocked") == [3, 4, 5]
    assert names == [f"{harness.CALL_SPAN} rows=3"]
    assert len(s.calls) == 1
    t0, t1, rows = s.calls[0]
    assert t0 <= t1 and rows == 3
    # every other attribute is the service's own, called without a span
    assert s.m_limbs == 4
    assert s.modexp([2], [5], 7) == [4]
    assert s.modexp.__self__ is svc
    assert len(s.calls) == 1 and len(names) == 1
