"""One run of one benchmark cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run builds the cell's configuration behind `AsyncFrontend`, warms
every bucket the cell's traffic can reach (set-up), drives the traffic
in a closed loop for `--seconds` and waits for the requests then in
flight (the window), compares the answers with the plain reference,
and prints one JSON line last on standard output.  With `--trace 1`
the profiler records a few whole service calls in the middle of the
window and the line carries the per-layer metrics; with `--trace 0` it
carries the end-to-end ones.

Everything is found by name: the cell in `BENCHMARK.json`, its
configuration in `configs/<config>.json` (and its tiny copy for the
CPU tests in `tests/data/tiny-<config>.json`), its operation in
`ops/<op>.py` (the service method spanned is the one the frontend
calls for it), its mix in `traffic/<traffic>.json` and each per-layer
metric's reader in `metrics/<name>.py`.  A new configuration enters as
new files and entries alone.
"""

from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import math
import random
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_MIN_SECONDS = 1.0         # the traced part of the window lasts at
TRACE_MIN_CALLS = 2             # least this long and holds this many calls
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
CALL_SPAN = "bench.call"        # host span around each service call


class NoDevice(RuntimeError):
    """The machine has no accelerator of the kind the cell needs."""


class MissingMetric(RuntimeError):
    """A per-layer metric that BENCHMARK.json declares for the cell read
    nothing in a traced run."""


def load_module(path: Path):
    """Import a file of the benchmark by path (they are not a package)."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A cell and everything it names, resolved from files."""
    workload: dict
    cfg: dict
    mix: object
    op: object
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, name: str, spec_path: Path = ROOT / "BENCHMARK.json"):
        import gen
        spec = json.loads(Path(spec_path).read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        w = cells[name]
        conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
        cfg = json.loads((ROOT / conf["file"]).read_text())
        mix = gen.Mix.load(BENCH / "traffic" / f"{w['traffic']}.json")
        op = load_module(BENCH / "ops" / f"{cfg['op']}.py")
        e2e = [m for m in spec["end_to_end"]
               if name in m.get("workloads", [name])]
        layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])]
        return cls(w, cfg, mix, op, e2e, layer)


class Spanned:
    """The service as the frontend sees it, with a host span and a
    record around each call of the endpoint `method` (the frontend
    makes one call per bucket); every other attribute is the
    service's own."""

    def __init__(self, service, method: str):
        import jax
        self._service = service
        self.calls: list[tuple[float, float, int]] = []
        meth = getattr(service, method)
        annotate = jax.profiler.TraceAnnotation

        def timed(*args, **kw):
            rows = len(args[0])
            t0 = time.perf_counter()
            with annotate(f"{CALL_SPAN} rows={rows}"):
                out = meth(*args, **kw)
            self.calls.append((t0, time.perf_counter(), rows))
            return out
        setattr(self, method, timed)

    def __getattr__(self, name):
        return getattr(self._service, name)


@dataclass
class Record:
    request: object
    t_submit: float
    t_done: float = math.inf
    result: object = None
    error: BaseException | None = None


@dataclass
class Run:
    """What the per-layer readers read (`metrics/<name>.py`)."""
    cfg: dict
    op: object
    window_s: float
    cycles: int = 0                 # frontend coalescing cycles
    coalesced: float = 0.0          # requests those cycles merged
    calls: int = 0                  # compiled bucket calls
    call_rows: int = 0              # true rows of those calls
    call_seconds: float = 0.0       # host time in those calls
    ctx_hits: int | None = None
    ctx_misses: int | None = None
    trace: object = None            # tracereduce.Reading of the traced part
    peaks: dict | None = None


def quantity(name: str) -> str:
    """What an end-to-end metric measures: its name, less a last
    `.<part>` that splits one quantity into metrics bounded for
    different cells (`latency_p95_ms.single` is `latency_p95_ms`)."""
    return name.rsplit(".", 1)[0]


def percentile(values: list[float], p: float) -> float:
    """Nearest-rank percentile (a failed request reads infinite)."""
    s = sorted(values)
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]


def reachable_buckets(service, mix) -> list[int]:
    return sorted({b for n in mix.request_sizes()
                   for _, _, b in service.batcher.plan(n)})


def service_counts(service) -> dict:
    st = service.stats()
    calls = sum(h["count"] for h in st.get("bucket_seconds", {}).values())
    secs = sum(h["sum"] for h in st.get("bucket_seconds", {}).values())
    ctx = st.get("ctx_cache")
    return {"calls": calls, "seconds": secs,
            "rows": st.get("rows_true", 0),
            "hits": ctx["hits"] if ctx else None,
            "misses": ctx["misses"] if ctx else None}


async def drive(frontend, op, stream, clients: int, seconds: float,
                on_window=None) -> tuple[list[Record], float, float]:
    """Closed loop: each client sends its next request when the last
    one returns, until `seconds` are up; then nothing more is sent and
    the requests in flight are waited for.  Returns (records, window
    start, time the last request returned)."""
    records: list[Record] = []
    t0 = time.perf_counter()
    t_end = t0 + seconds

    async def client():
        while time.perf_counter() < t_end:
            req = stream.next()
            rec = Record(req, time.perf_counter())
            records.append(rec)
            try:
                rec.result = await frontend.submit(op.OP, *req.cols,
                                                   v=req.v)
            except Exception as exc:     # counted as failed, not fatal
                rec.error = exc
            rec.t_done = time.perf_counter()

    tasks = [asyncio.create_task(client()) for _ in range(clients)]
    side = [asyncio.create_task(on_window(t0, t_end))] if on_window else []
    await asyncio.gather(*tasks)
    t_close = time.perf_counter()
    await asyncio.gather(*side)
    return records, t0, t_close


class Tracer:
    """Traces part of the middle of the window: from about its midpoint
    until at least TRACE_MIN_SECONDS have passed and TRACE_MIN_CALLS
    service calls have begun and ended inside the trace.  The profiler
    takes about a tenth of a millisecond per device op to write its
    trace, and a modexp call runs some 400,000 ops, so the traced part
    is kept to a few calls."""

    def __init__(self, out: Path, calls: list):
        self.out = out
        self.calls = calls              # Spanned.calls, appended live

    def whole_calls(self, a: float, now: float) -> int:
        n = 0
        for c0, c1, _ in reversed(self.calls):
            if c0 < a:
                break
            n += c1 <= now
        return n

    async def __call__(self, t0: float, t_end: float) -> None:
        import jax
        loop = asyncio.get_running_loop()
        await asyncio.sleep(max(0.0, (t_end - t0 - TRACE_MIN_SECONDS) / 2))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        await loop.run_in_executor(
            None, lambda: jax.profiler.start_trace(
                str(self.out), profiler_options=opts))
        a = b = time.perf_counter()
        while b < t_end and (b - a < TRACE_MIN_SECONDS or
                             self.whole_calls(a, b) < TRACE_MIN_CALLS):
            await asyncio.sleep(0.02)
            b = time.perf_counter()
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        log(f"[bench] trace: {b - a:.3f} s traced, stopped and written in "
            f"{time.perf_counter() - b:.3f} s")


def enable_compile_cache(jax) -> str:
    import os
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return env or str(CACHE_DIR)


class CompileCounter:
    """Counts programs lowered (traced and compiled or loaded from the
    cache) while `armed`."""

    def __init__(self, jax):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.armed and name.endswith("jaxpr_to_mlir_module_duration"):
            self.count += 1


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def check_answers(op, records: list[Record], seed: int) -> tuple[int, int]:
    """(compared, wrong) over every answered row, or a seeded sample of
    op.CHECK_ROWS of them."""
    rows = [(k, i) for k, r in enumerate(records) if r.error is None
            for i in range(r.request.rows)]
    if op.CHECK_ROWS is not None and len(rows) > op.CHECK_ROWS:
        rows = random.Random(f"check:{seed}").sample(rows, op.CHECK_ROWS)
    answers = {}
    wrong = 0
    for k, i in rows:
        if k not in answers:
            answers[k] = op.result_rows(records[k].result)
        req = records[k].request
        got = answers[k][i] if i < len(answers[k]) else None
        wrong += got != op.reference_row(req.cols, req.v, i)
    return len(rows), wrong


def off_path(cfg: dict, service, frontend) -> int:
    """Buckets that planned another kernel path than the configuration
    states or degraded, plus degradations the frontend counted."""
    bad = sum(1 for p in getattr(service, "kernel_plans", {}).values()
              if p.impl != cfg["kernel_impl"] or p.degraded_from)
    return bad + int(sum(s.value for s in
                         frontend.metrics.degraded.series()))


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, need_chip: bool = True, wrap=None,
        control: bool = False, spec_path: Path = ROOT / "BENCHMARK.json"
        ) -> dict:
    """One run of cell `name`; returns the result line as a dict.

    wrap:    optional function service -> service, put between the
             frontend and the service (tests break the timed path
             with it)
    control: serve the operation's control in the program's place
    """
    cell = Cell.load(name, spec_path)
    cfg, mix, op = cell.cfg, cell.mix, cell.op
    import jax
    devs = jax.devices()
    dev = devs[0]
    if need_chip and (dev.platform != "tpu"
                      or len(devs) < cell.workload["chips"]):
        raise NoDevice(f"cell {name} needs {cell.workload['chips']} TPU "
                       f"chip(s); JAX found {len(devs)} x {dev.platform}")
    import roofline
    try:
        peaks = roofline.load_peaks(dev.device_kind)
    except KeyError:
        if need_chip:
            raise
        peaks = None
    log(f"[bench] {name}: {len(devs)} x {dev.device_kind} "
        f"({dev.platform})")
    compiles = CompileCounter(jax)
    from repro.serving.frontend import _OPS, AsyncFrontend
    import gen

    service = op.build_service(cfg)
    if control:
        service = op.Control(service)
    if wrap is not None:
        service = wrap(service)
    spanned = Spanned(service, _OPS[op.OP][0])
    stream = gen.Stream(op, cfg, mix, seed)

    # -- set-up: the cache state, every reachable bucket, the frontend
    t = time.perf_counter()
    op.warm_keys(spanned, stream, cfg)
    log(f"[bench] set-up: keys warmed in {time.perf_counter() - t:.3f} s")
    key = stream.keys[0] if stream.keys else None
    for bucket in reachable_buckets(service, mix):
        times = []
        for _ in range(2):
            t = time.perf_counter()
            op.call(spanned, stream.rows.take(bucket, key), key)
            times.append(time.perf_counter() - t)
        log(f"[bench] set-up: bucket {bucket} first call {times[0]:.3f} s"
            f" (trace, compile or cache load), second {times[1]:.3f} s")
    async def warm():
        async with AsyncFrontend(spanned) as fe:
            await asyncio.gather(*(fe.submit(op.OP, *r.cols, v=r.v)
                                   for r in (stream.next() for _ in
                                             range(mix.clients))))
    asyncio.run(warm())

    # -- the window
    tracer = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = Tracer(TRACE_DIR, spanned.calls)
    before = service_counts(spanned)
    n_spans = len(spanned.calls)

    async def window():
        fe = AsyncFrontend(spanned)
        async with fe:
            out = await drive(fe, op, stream, mix.clients, seconds,
                              on_window=tracer)
        return fe, out

    gc.collect()
    compiles.armed = True
    t_window = time.perf_counter()
    frontend, (records, t0, t_close) = asyncio.run(window())
    compiles.armed = False
    after = service_counts(spanned)
    setup_s = t_window - t_start

    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    fm = frontend.metrics
    coal = fm.coalesced.series()[0] if fm.coalesced.series() else None
    reading = Run(
        cfg=cfg, op=op, window_s=t_close - t0,
        cycles=coal.count if coal else 0,
        coalesced=coal.value if coal else 0.0,
        calls=after["calls"] - before["calls"],
        call_rows=after["rows"] - before["rows"],
        call_seconds=after["seconds"] - before["seconds"],
        ctx_hits=None if after["hits"] is None
        else after["hits"] - before["hits"],
        ctx_misses=None if after["misses"] is None
        else after["misses"] - before["misses"],
        peaks=peaks)
    off = off_path(cfg, service, frontend)
    window_calls = spanned.calls[n_spans:]
    del frontend, spanned, service
    gc.collect()

    result: dict = {}
    if trace:
        import tracereduce as TR
        t = time.perf_counter()
        traced = TR.read_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        reading.trace = traced
        device["busy_s"] = traced.busy_s()
        device["window_s"] = traced.window_s()
        result["breakdown"] = traced.breakdown()
        log(f"[bench] trace: {len(traced.ops)} device ops read and reduced"
            f" in {time.perf_counter() - t:.3f} s")
        n_traced = len(traced.spans)
        log(f"[bench] trace: {n_traced} whole calls in "
            f"{traced.window_s():.3f} s of the traced part, "
            f"{traced.kernel_count()} kernel events inside them "
            f"({traced.kernel_count() / max(n_traced, 1):.1f} a call; cost "
            f"model {op.model_launches_per_call(cfg)}), kernel rule "
            f"'{TR.KERNEL_RULE}'")

    # -- the comparison, once the window's state is gone
    t = time.perf_counter()
    checked, wrong = check_answers(op, records, seed)
    log(f"[bench] compared {checked} answered rows in "
        f"{time.perf_counter() - t:.3f} s")
    failed = sum(r.error is not None for r in records)
    for r in records:
        if r.error is not None:
            log(f"[bench] request failed: {r.error!r}")
            break
    done = [r for r in records if r.error is None]
    lat = [(r.t_done - r.t_submit) * 1e3 if r.error is None else math.inf
           for r in records]
    e2e = {
        "ops_per_s": sum(r.request.rows for r in done) / (t_close - t0),
        "latency_p50_ms": percentile(lat, 50) if lat else math.inf,
        "latency_p95_ms": percentile(lat, 95) if lat else math.inf,
        "setup_s": setup_s,
    }
    log(f"[bench] window {t_close - t0:.3f} s ({seconds} s of sending, "
        f"then the drain): {len(records)} requests, {len(done)} answered,"
        f" {len(window_calls)} service calls, {compiles.count} programs "
        f"lowered in the window")
    if window_calls:
        longest = max(c1 - c0 for c0, c1, _ in window_calls)
        gaps = [b[0] - a[1] for a, b in zip(window_calls, window_calls[1:])]
        log(f"[bench] longest service call {longest:.3f} s, longest gap "
            f"between calls {max(gaps, default=0.0):.3f} s, slowest "
            f"reply {max(lat, default=0.0) / 1e3:.3f} s")
    metrics = {}
    missing = []
    if trace:
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(reading)
            if value is None:
                missing.append(m["name"])
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[quantity(m["name"])],
                                  "unit": m["unit"]}
    for k, v in e2e.items():
        log(f"[bench] {k} {v}")
    compared = {
        "wrong_answers": {"value": wrong, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
        "off_path_buckets": {"value": off, "limit": 0},
        "window_compiles": {"value": compiles.count, "limit": 0},
    }
    correct = (checked > 0 and all(c["value"] <= c["limit"]
                                   for c in compared.values()))
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    out.update(result)
    out["compared"] = compared
    log(f"[bench] compared rows: {checked}")
    for k, c in compared.items():
        log(f"compared {k} {c['value']} limit {c['limit']}")
    if missing:
        raise MissingMetric(f"cell {name} declares per-layer metrics that "
                            f"read nothing in the traced run: {missing}")
    return out
