"""Dependency-free metrics core: counters, gauges, histograms with
labeled series, JSON / line-protocol export, and host spans on the
profiler's clock.

Design constraints (and why):

  * No global mutable singletons.  Every owner (a service instance, a
    benchmark run) constructs its own `Registry`; nothing here is
    process-global, so two services never alias counters and nothing
    can leak into traced code by accident.
  * Host-side only.  Metrics are recorded OUTSIDE jit boundaries --
    request counts and wall times around compiled calls, structural
    facts once at trace time (see `utils/jaxpr_stats.py:trace_profile`).
    Recording a traced value would silently bake one trace's sample
    into the executable; the registry only accepts plain Python
    numbers (`float()` coercion raises on tracers).
  * stdlib only at import time.  `span` imports `jax.profiler` on
    first use, so this module is importable (and the CI docs tooling
    can use it) without a backend.

Label model: a metric is declared once with a fixed tuple of label
NAMES; each distinct label-value assignment is one monotonic series
(`Counter.labels(bucket=64).inc()`).  Export is deterministic (sorted
by metric name, then label values) in two formats: `Registry.to_json`
(nested dicts, the snapshot schema) and `Registry.to_lines`
(`name{k=v,...} value` line protocol for quick grepping/ingestion).
"""

from __future__ import annotations

import contextlib
import functools
import json
import time


def _coerce(value) -> float:
    """Accept plain Python/numpy numbers; reject jax tracers.

    float() on a jax tracer raises ConcretizationTypeError, which is
    exactly the behavior we want -- recording a traced value into a
    host-side registry is a bug (it would run at trace time, once,
    not per request)."""
    return float(value)


class _Series:
    """One labeled time series of a metric."""

    __slots__ = ("labels", "value")

    def __init__(self, labels: dict):
        self.labels = labels
        self.value = 0.0


class CounterSeries(_Series):
    def inc(self, amount=1) -> None:
        amount = _coerce(amount)
        if amount < 0:
            raise ValueError("counters only go up; use a Gauge")
        self.value += amount


class GaugeSeries(_Series):
    def set(self, value) -> None:
        self.value = _coerce(value)

    def inc(self, amount=1) -> None:
        self.value += _coerce(amount)

    def dec(self, amount=1) -> None:
        self.value -= _coerce(amount)


# Default latency-oriented boundaries (seconds): ~100us .. ~100s.
DEFAULT_BUCKETS = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1,
                   1.0, 3.0, 10.0, 30.0, 100.0)


class HistogramSeries(_Series):
    __slots__ = ("labels", "value", "bounds", "counts", "count")

    def __init__(self, labels: dict, bounds: tuple):
        super().__init__(labels)
        self.bounds = bounds
        self.counts = [0] * (len(bounds) + 1)   # last = +inf overflow
        self.count = 0
        self.value = 0.0                        # running sum

    def observe(self, value) -> None:
        value = _coerce(value)
        i = 0
        while i < len(self.bounds) and value > self.bounds[i]:
            i += 1
        self.counts[i] += 1
        self.count += 1
        self.value += value

    @contextlib.contextmanager
    def time(self):
        """Monotonic-clock timer: `with hist.time(): run()`."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(time.perf_counter() - t0)


_KINDS = {"counter": CounterSeries, "gauge": GaugeSeries,
          "histogram": HistogramSeries}


class Metric:
    """A named family of series sharing one set of label names."""

    def __init__(self, name: str, kind: str, help: str = "",
                 labelnames: tuple = (), buckets: tuple = DEFAULT_BUCKETS):
        if kind not in _KINDS:
            raise ValueError(f"unknown metric kind {kind!r}")
        self.name = name
        self.kind = kind
        self.help = help
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(buckets)
        self._series: dict[tuple, _Series] = {}

    def labels(self, **labelvalues) -> _Series:
        """The series for one label-value assignment (created on first
        use).  Label names must match the declaration exactly."""
        if tuple(sorted(labelvalues)) != tuple(sorted(self.labelnames)):
            raise ValueError(
                f"{self.name}: got labels {sorted(labelvalues)}, "
                f"declared {sorted(self.labelnames)}")
        key = tuple(labelvalues[n] for n in self.labelnames)
        if key not in self._series:
            cls = _KINDS[self.kind]
            labels = dict(zip(self.labelnames, key))
            self._series[key] = (cls(labels, self.buckets)
                                 if self.kind == "histogram"
                                 else cls(labels))
        return self._series[key]

    # convenience: an unlabeled metric acts as its single series
    def _default(self) -> _Series:
        if self.labelnames:
            raise ValueError(f"{self.name} is labeled; call .labels()")
        return self.labels()

    def inc(self, amount=1):
        return self._default().inc(amount)

    def dec(self, amount=1):
        return self._default().dec(amount)

    def set(self, value):
        return self._default().set(value)

    def observe(self, value):
        return self._default().observe(value)

    def time(self):
        return self._default().time()

    def series(self) -> list[_Series]:
        return [self._series[k] for k in sorted(self._series)]


class Registry:
    """Instance-scoped metric registry.  Declaring the same name twice
    returns the existing metric (and errors on a kind mismatch), so
    helper layers can idempotently declare what they record."""

    def __init__(self):
        self._metrics: dict[str, Metric] = {}

    def _declare(self, name, kind, help, labelnames, **kw) -> Metric:
        m = self._metrics.get(name)
        if m is not None:
            if m.kind != kind or m.labelnames != tuple(labelnames):
                raise ValueError(
                    f"metric {name!r} re-declared as {kind}"
                    f"{tuple(labelnames)}; was {m.kind}{m.labelnames}")
            return m
        m = Metric(name, kind, help, labelnames, **kw)
        self._metrics[name] = m
        return m

    def counter(self, name, help="", labelnames=()) -> Metric:
        return self._declare(name, "counter", help, labelnames)

    def gauge(self, name, help="", labelnames=()) -> Metric:
        return self._declare(name, "gauge", help, labelnames)

    def histogram(self, name, help="", labelnames=(),
                  buckets=DEFAULT_BUCKETS) -> Metric:
        return self._declare(name, "histogram", help, labelnames,
                             buckets=buckets)

    def get(self, name) -> Metric | None:
        return self._metrics.get(name)

    # -- export ----------------------------------------------------------

    def collect(self) -> list[dict]:
        """Deterministic plain-data dump of every series."""
        out = []
        for name in sorted(self._metrics):
            m = self._metrics[name]
            series = []
            for s in m.series():
                row = {"labels": s.labels, "value": s.value}
                if m.kind == "histogram":
                    row.update({"count": s.count, "sum": s.value,
                                "bounds": list(s.bounds),
                                "bucket_counts": list(s.counts)})
                    del row["value"]
                series.append(row)
            out.append({"name": name, "kind": m.kind, "help": m.help,
                        "series": series})
        return out

    def to_json(self, **json_kw) -> str:
        return json.dumps(self.collect(), sort_keys=True, **json_kw)

    def to_lines(self) -> list[str]:
        """`name{k=v,...} value` line protocol (histograms emit _count
        and _sum lines plus cumulative le-bucket lines)."""
        def tag(name, lbl):
            return f"{name}{{{lbl}}}" if lbl else name

        lines = []
        for fam in self.collect():
            for s in fam["series"]:
                lbl = ",".join(f"{k}={v}" for k, v in
                               sorted(s["labels"].items()))
                if fam["kind"] == "histogram":
                    cum = 0
                    for bound, n in zip(s["bounds"] + [float("inf")],
                                        s["bucket_counts"]):
                        cum += n
                        blbl = (lbl + "," if lbl else "") + f"le={bound}"
                        lines.append(
                            f"{tag(fam['name'] + '_bucket', blbl)} {cum}")
                    lines.append(f"{tag(fam['name'] + '_count', lbl)} "
                                 f"{s['count']}")
                    lines.append(f"{tag(fam['name'] + '_sum', lbl)} "
                                 f"{s['sum']}")
                else:
                    v = s["value"]
                    lines.append(f"{tag(fam['name'], lbl)} "
                                 f"{int(v) if v == int(v) else v}")
        return lines


def merged_lines(*registries) -> list[str]:
    """Line-protocol export across several registries (e.g. a serving
    frontend's queue/failure families next to the wrapped service's
    request families); the serving tier's one-stop metric export."""
    out = []
    for reg in registries:
        out.extend(reg.to_lines())
    return out


@functools.cache
def _trace_annotation():
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


class span:
    """A host span on the profiler's clock, always on.

    From construction until `end()` (or the end of a `with` block) the
    span is a `jax.profiler.TraceAnnotation` named `name`, so a
    profiler session records it on the same clock as the device ops;
    with no session active it costs a few microseconds.  Given a
    histogram series, or a tuple of them, it also observes its length
    in seconds on each.

    Host-side only: never open one inside traced code.  `end()` may
    come from a later coroutine on the same thread (two spans may end
    out of order), and only its first call counts."""

    __slots__ = ("_annotation", "_series", "_t0")

    def __init__(self, name: str, series=None):
        self._series = (() if series is None else
                        series if isinstance(series, tuple) else (series,))
        self._annotation = _trace_annotation()(name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()

    def end(self) -> None:
        if self._annotation is None:
            return
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        for s in self._series:
            s.observe(seconds)

    def __enter__(self) -> "span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()
