"""Modular-arithmetic serving frontend on cached Barrett contexts.

`ModArithService` keys a bounded per-modulus cache of device-resident
`BarrettContext`s (one Newton-iterated shinv each) and serves `reduce`,
`modmul`, and `modexp` over Python-int request batches.  The first
request against a modulus pays the precompute; every later request --
and every internal step of a modexp ladder -- reuses the cached shifted
inverse, so a reduction costs two truncated multiplications instead of
a full division.  Bucketing, padding, and mesh sharding are shared with
`BigintDivisionService` via `serving.batching`; the context is
replicated across the mesh while the request batch is sharded.

Observability (docs/observability.md): every (op, bucket) compile
captures a STATIC structural profile off the traced program (Pallas
launches incl. the scan-trip-weighted runtime count, XLA glue eqns,
total eqns -- `utils/jaxpr_stats.trace_profile`) plus the
`KernelPlan`; runtime counters cover requests, true-vs-padded rows,
per-bucket latency, and the Barrett context cache
(hits/misses/evictions).  `stats()` returns the runtime counters,
`snapshot()` the merged static + runtime profile that
`obs/report.py` renders as a measured-vs-model table.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bigint as bi
from repro.core import modarith as MA
from repro.obs import telemetry as OBS
from repro.utils import jaxpr_stats as JS
from . import batching as BT
from . import errors as E


class ModArithService:
    """Batched modular arithmetic at one (modulus-storage) precision.

    m_limbs:    storage width of moduli/residues (values < B^m_limbs)
    e_limbs:    storage width of modexp exponents (default m_limbs)
    impl:       kernel path ("scan" | "blocked" | "pallas" |
                "pallas_batched" | "pallas_fused"; None = backend
                default -- pallas_fused on TPU runs each Barrett
                reduction as ONE fused launch, see kernels/fused.py)
    windowed:   size-bucketed Newton refinement in the precompute
    window_bits: modexp ladder window (must divide 16)
    max_cached_moduli: LRU bound on device-resident contexts
    capture_profiles: trace a static structural profile at every
                (op, bucket) compile (cheap at service precisions;
                disable for very large m where a trace is minutes)
    """

    def __init__(self, m_limbs: int, mesh=None, impl: str | None = None,
                 windowed: bool = True, window_bits: int = 4,
                 e_limbs: int | None = None,
                 batch_buckets=(64, 256, 1024),
                 max_cached_moduli: int = 64,
                 capture_profiles: bool = True, faults=None):
        self.m = m_limbs
        self.e_limbs = e_limbs if e_limbs is not None else m_limbs
        self.mesh = mesh
        self.impl = impl
        self.windowed = windowed
        self.window_bits = window_bits
        self.capture_profiles = capture_profiles
        self.batcher = BT.Batcher(batch_buckets)
        self._fns = BT.CompiledBuckets()
        # per-bucket kernel geometry, recorded when the bucket compiles
        self.kernel_plans: dict[int, BT.KernelPlan] = {}
        # per-bucket static structural profiles, keyed [bucket][op],
        # captured at the same moment (a CompiledBuckets miss)
        self.static_profiles: dict[int, dict] = {}
        self._ctxs: OrderedDict[int, MA.BarrettContext] = OrderedDict()
        self._ctx_lock = threading.RLock()
        self.max_cached = max_cached_moduli
        self.ctx_hits = 0
        self.ctx_misses = 0
        self.ctx_evictions = 0
        self.telemetry = BT.ServiceMetrics()
        self._ctx_metric = self.telemetry.registry.counter(
            "ctx_cache_total", "Barrett context cache events", ("event",))
        self._precompute = jax.jit(partial(
            MA.barrett_precompute, impl=impl, windowed=windowed))
        self.faults = faults            # serving/faults.FaultInjector

    def set_fault_injector(self, faults) -> None:
        """Install (or clear, with None) a fault injector; the
        injection sites below are exact no-ops without one."""
        self.faults = faults

    def _fire(self, site: str, **labels) -> None:
        if self.faults is not None:
            self.faults.fire(site, **labels)

    # -- per-modulus context cache ----------------------------------------

    def check_modulus(self, v) -> None:
        if isinstance(v, bool) or not isinstance(v, int):
            raise E.OperandTypeError(
                f"modulus: expected int, got {type(v).__name__}")
        if v <= 0:
            raise E.InvalidRequest("modulus must be positive")
        if v >= bi.BASE ** self.m:
            raise E.OperandRangeError(
                f"modulus does not fit in {self.m} limbs")

    def context(self, v: int) -> MA.BarrettContext:
        """Device-resident Barrett context for v, LRU-cached.

        Thread-safe: the lock covers lookup, precompute, insert, and
        eviction, so concurrent requests against one modulus cannot
        double-precompute the shinv or corrupt the OrderedDict (a
        first-touch precompute serializes other moduli too -- the
        price of exactly-once precompute)."""
        self.check_modulus(v)
        with self._ctx_lock:
            if v in self._ctxs:
                self._ctxs.move_to_end(v)
                self.ctx_hits += 1
                self._ctx_metric.labels(event="hit").inc()
                return self._ctxs[v]
            self._fire("precompute")
            self.ctx_misses += 1
            self._ctx_metric.labels(event="miss").inc()
            with OBS.annotate("modexp_service/precompute"):
                ctx = self._precompute(
                    jnp.asarray(bi.from_int(v, self.m)))
            self._ctxs[v] = ctx
            while len(self._ctxs) > self.max_cached:
                self._ctxs.popitem(last=False)
                self.ctx_evictions += 1
                self._ctx_metric.labels(event="eviction").inc()
            return ctx

    # -- compiled per-bucket executables ----------------------------------

    def _zero_ctx(self) -> MA.BarrettContext:
        """Shape-only BarrettContext for structural tracing (no
        precompute -- trace_profile never executes)."""
        return MA.BarrettContext(
            v=jnp.zeros((self.m,), bi.DTYPE),
            mu=jnp.zeros((MA.barrett_width(self.m),), jnp.uint32),
            k=jnp.zeros((), jnp.int32))

    def _fn(self, op: str, bucket: int, impl: str | None = None):
        eff = BT.resolve_impl(impl or self.impl)

        def build():
            self._fire("compile", op=op, bucket=bucket, impl=eff)
            # widest internal product: x * mu at the Barrett working width
            plan = BT.kernel_plan(BT.device_rows(bucket, self.mesh),
                                  MA.barrett_width(self.m), eff)
            req = BT.resolve_impl(self.impl)
            if eff != req:
                plan = plan._replace(degraded_from=req)
            self.kernel_plans[bucket] = plan
            impl = plan.impl
            if op == "reduce":
                f = partial(MA.reduce_shared, impl=impl)
                batched = (1,)
                widths = (2 * self.m,)
            elif op == "modmul":
                f = partial(MA.modmul_shared, impl=impl)
                batched = (1, 2)
                widths = (self.m, self.m)
            elif op == "modexp":
                f = partial(MA.modexp_shared, impl=impl,
                            window_bits=self.window_bits)
                batched = (1, 2)
                widths = (self.m, self.e_limbs)
            else:
                raise ValueError(op)
            if self.capture_profiles:
                zs = [jnp.zeros((bucket, w), jnp.uint32) for w in widths]
                self.static_profiles.setdefault(bucket, {})[op] = \
                    JS.trace_profile(f, self._zero_ctx(), *zs)
            return BT.sharded_jit(f, self.mesh, batched,
                                  n_args=1 + len(widths), n_out=1)
        return self._fns.get((op, bucket, eff), build)

    def profile_bucket(self, op: str, bucket: int) -> dict:
        """Force-compile one (op, bucket) executable (trace only, no
        execution) and return the bucket's static profiles."""
        self._fn(op, bucket)
        return self.static_profiles.get(bucket, {})

    # column names and operand limits per op, for index-carrying
    # validation messages (exponents are bounded by the ladder's
    # e_limbs storage width, not the modulus width)
    def _op_schema(self, op: str):
        lim = bi.BASE ** self.m
        if op == "reduce":
            lim2 = bi.BASE ** (2 * self.m)
            return (("x", lim2, f"B^{2 * self.m}"),)
        if op == "modmul":
            return (("a", lim, f"B^{self.m}"),
                    ("b", lim, f"B^{self.m}"))
        if op == "modexp":
            return (("a", lim, f"B^{self.m}"),
                    ("e", bi.BASE ** self.e_limbs,
                     f"B^{self.e_limbs}"))
        raise E.InvalidRequest(f"unknown op {op!r} for ModArithService")

    def validate(self, op: str, columns, v=None) -> int:
        """Full request validation (types, ranges, column lengths,
        modulus); returns the request length.  Raises serving.errors
        InvalidRequest subtypes carrying the offending index."""
        schema = self._op_schema(op)
        if len(columns) != len(schema):
            raise E.InvalidRequest(
                f"{op} takes {len(schema)} columns, got {len(columns)}")
        n = E.check_lengths(columns, names=[s[0] for s in schema])
        for col, (name, lim, what) in zip(columns, schema):
            E.check_operands(name, col, lim, what)
        if v is not None:
            self.check_modulus(v)
        return n

    def _run(self, op: str, v: int, columns, widths, *,
             impl: str | None = None) -> list[int]:
        """Pack int columns to limb batches, run per bucket, unpack.

        `impl` overrides the service impl for this call (the serving
        frontend's degradation ladder; bit-identical by contract)."""
        n = self.validate(op, columns, v)
        if n == 0:
            return []
        self.telemetry.record_request(op, n)
        ctx = self.context(v)
        out: list[int] = []
        for lo, hi, bucket in self.batcher.plan(n):
            eff = BT.resolve_impl(impl or self.impl)
            self._fire("transfer", op=op, bucket=bucket)
            arrs = [jnp.asarray(bi.batch_from_ints(
                        BT.pad_ints(col[lo:hi], bucket, 0), w))
                    for col, w in zip(columns, widths)]
            fn = self._fn(op, bucket, impl)
            self.telemetry.record_rows(bucket, hi - lo)
            with OBS.annotate(f"modexp_service/{op}/b{bucket}"), \
                    self.telemetry.chunk_timer(op, bucket):
                self._fire("execute", op=op, bucket=bucket, impl=eff)
                res = np.asarray(fn(ctx, *arrs))
            out += bi.batch_to_ints(res[:hi - lo])
        return out

    # -- public entry points ----------------------------------------------

    def reduce(self, xs: list[int], v: int, *,
               impl: str | None = None) -> list[int]:
        """[x mod v] for double-width x (x < B^(2 m_limbs))."""
        return self._run("reduce", v, [xs], [2 * self.m], impl=impl)

    def modmul(self, a: list[int], b: list[int], v: int, *,
               impl: str | None = None) -> list[int]:
        """[(a_i * b_i) mod v] for a_i, b_i < B^m_limbs."""
        return self._run("modmul", v, [a, b], [self.m, self.m],
                         impl=impl)

    def modexp(self, a: list[int], e: list[int], v: int, *,
               impl: str | None = None) -> list[int]:
        """[pow(a_i, e_i, v)] -- fixed-window ladder, one cached shinv."""
        return self._run("modexp", v, [a, e], [self.m, self.e_limbs],
                         impl=impl)

    # -- introspection ----------------------------------------------------

    def stats(self) -> dict:
        """Runtime counters only (see `snapshot` for the merged view)."""
        out = self.telemetry.stats()
        total = self.ctx_hits + self.ctx_misses
        out["ctx_cache"] = {
            "hits": self.ctx_hits,
            "misses": self.ctx_misses,
            "evictions": self.ctx_evictions,
            "size": len(self._ctxs),
            "hit_rate": self.ctx_hits / total if total else 0.0,
        }
        out["bucket_compiles"] = self._fns.misses
        out["bucket_reuses"] = self._fns.hits
        return out

    def snapshot(self) -> dict:
        """Merged static + runtime profile: per-bucket KernelPlan
        geometry and per-op structural trace counts alongside the
        lifetime runtime counters.  Render with
        `obs/report.py:render_measured_vs_model`."""
        from repro.kernels import ops as K
        buckets = {}
        for b in sorted(set(self.kernel_plans) | set(self.static_profiles)):
            entry = {}
            if b in self.kernel_plans:
                entry["plan"] = self.kernel_plans[b]._asdict()
            if b in self.static_profiles:
                entry["static"] = self.static_profiles[b]
            buckets[b] = entry
        return {
            "service": "modarith",
            "m_limbs": self.m,
            "e_limbs": self.e_limbs,
            "window_bits": self.window_bits,
            "impl": self.impl or K.default_impl(),
            "buckets": buckets,
            "runtime": self.stats(),
        }
