"""Modular-arithmetic serving frontend on a slab of Barrett contexts.

`ModArithService` serves `reduce`, `modmul`, and `modexp` over
Python-int request batches whose rows may each name their own modulus
(`v`: one int for every row, or one int per row).  The Barrett
contexts (one Newton-iterated shinv each) live on the device in one
slab of `max_cached_moduli` slots; an LRU maps each modulus to its
slot.  A call looks its rows' moduli up, runs ONE batched precompute
for all of its cold moduli and writes them into their slots, then runs
the bucket's program, which gathers each row's context by slot index:
a reduction costs two truncated multiplications instead of a full
division, and requests over many keys share one launch sequence.  One
key is the case where every row names the same slot.  Bucketing,
padding, and mesh sharding are shared with `BigintDivisionService` via
`serving.batching`; the slab is replicated across the mesh while the
request batch and its slot indices are sharded.

Observability (docs/observability.md): every (op, bucket) compile
captures a STATIC structural profile off the traced program (Pallas
launches incl. the scan-trip-weighted runtime count, XLA glue eqns,
total eqns -- `utils/jaxpr_stats.trace_profile`) plus the
`KernelPlan`; runtime counters cover requests, true-vs-padded rows,
per-bucket latency, and the context cache (hits/misses/evictions,
distinct moduli per call, cold moduli per batched precompute); each
host phase of a call (validate, contexts and the precompute within
it, pack, execute, unpack) is a `service.<phase>` span on the
profiler's clock.  `stats()` returns the runtime counters,
`snapshot()` the merged static + runtime profile that `obs/report.py`
renders as a measured-vs-model table.
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
from repro.utils import jaxpr_stats as JS
from . import batching as BT
from . import errors as E

# Compiled batched precomputes, shared by every service of the process:
# the program depends only on its key (slots, m, pad, impl, windowed).
_FILLS = BT.CompiledBuckets()


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
    max_cached_moduli: slots of the device-resident context slab (the
                LRU bound); a call chunk with more distinct moduli is
                split so that each part fits
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
        if max_cached_moduli < 1:
            raise ValueError("max_cached_moduli must be at least 1")
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
        # modulus -> slot of the slab, least recently used first
        self._ctxs: OrderedDict[int, int] = OrderedDict()
        self._free = list(range(max_cached_moduli - 1, -1, -1))
        self._slab: MA.ContextSlab | None = None    # made at first fill
        self._ctx_lock = threading.RLock()
        self.max_cached = max_cached_moduli
        # a call's cold moduli are at most its rows and the slab's slots
        self._fill_pad = self.batcher.bucket_for(max_cached_moduli)
        self.ctx_hits = 0
        self.ctx_misses = 0
        self.ctx_evictions = 0
        self.telemetry = BT.ServiceMetrics()
        reg = self.telemetry.registry
        self._ctx_metric = reg.counter(
            "ctx_cache_total", "Barrett context cache events", ("event",))
        self._call_moduli = reg.histogram(
            "call_moduli", "distinct moduli per compiled bucket call",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
        self._precompute_rows = reg.histogram(
            "ctx_precompute_rows", "cold moduli per batched precompute",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
        self.faults = faults            # serving/faults.FaultInjector

    def set_fault_injector(self, faults) -> None:
        """Install (or clear, with None) a fault injector; the
        injection sites below are exact no-ops without one."""
        self.faults = faults

    def _fire(self, site: str, **labels) -> None:
        if self.faults is not None:
            self.faults.fire(site, **labels)

    # -- the context slab -------------------------------------------------

    def check_modulus(self, v, name: str = "modulus") -> None:
        if isinstance(v, bool) or not isinstance(v, int):
            raise E.OperandTypeError(
                f"{name}: expected int, got {type(v).__name__}")
        if v <= 0:
            raise E.InvalidRequest(f"{name} must be positive")
        if v >= bi.BASE ** self.m:
            raise E.OperandRangeError(
                f"{name} does not fit in {self.m} limbs")

    def _fill_fn(self):
        """The batched precompute into the slab, padded to the smallest
        bucket that holds the most cold moduli a call can have (the
        slab's slots or the largest bucket, whichever is fewer).  One
        program, built with the first bucket so that none compiles
        while serving, and shared with every service of the same
        shape."""
        eff = BT.resolve_impl(self.impl)
        pad = self._fill_pad

        def build():
            u32, i32 = jnp.uint32, jnp.int32
            slab = jax.eval_shape(
                partial(MA.empty_slab, self.max_cached, self.m))
            fill = jax.jit(partial(MA.precompute_into, impl=eff,
                                   windowed=self.windowed))
            return fill.lower(
                slab, jax.ShapeDtypeStruct((pad, self.m), u32),
                jax.ShapeDtypeStruct((pad,), i32)).compile()
        return _FILLS.get((self.max_cached, self.m, pad, eff,
                           self.windowed), build)

    def _slots(self, op: str, moduli: list[int]):
        """(slab, slot of each row) for a call whose distinct moduli fit
        the slab.  Hits are touched first, so the LRU victims of the
        misses are never moduli of this call; the misses' contexts are
        computed by one batched precompute (dispatched, not awaited)
        and written into their slots in a new slab, which this call
        keeps even if a later call evicts them."""
        distinct = list(dict.fromkeys(moduli))
        with self._ctx_lock:
            cold = [v for v in distinct if v not in self._ctxs]
            if cold:
                self._fire("precompute")
            for v in distinct:
                if v in self._ctxs:
                    self._ctxs.move_to_end(v)
            for v in cold:
                if self._free:
                    slot = self._free.pop()
                else:
                    _, slot = self._ctxs.popitem(last=False)
                    self.ctx_evictions += 1
                    self._ctx_metric.labels(event="eviction").inc()
                self._ctxs[v] = slot
            hits = len(distinct) - len(cold)
            self.ctx_hits += hits
            self.ctx_misses += len(cold)
            if hits:
                self._ctx_metric.labels(event="hit").inc(hits)
            if cold:
                self._ctx_metric.labels(event="miss").inc(len(cold))
                self._precompute_rows.observe(len(cold))
                try:
                    with self.telemetry.phase(op, "precompute"):
                        self._fill(cold)
                except BaseException:
                    # no context was written: free the misses' slots
                    for v in cold:
                        self._free.append(self._ctxs.pop(v))
                    raise
            return self._slab, [self._ctxs[v] for v in moduli]

    def _fill(self, cold: list[int]) -> None:
        """Precompute `cold` (held under the lock) into their slots."""
        pad = self._fill_pad
        slots = [self._ctxs[v] for v in cold]
        vs = jnp.asarray(bi.batch_from_ints(
            BT.pad_ints(cold, pad, cold[0]), self.m))
        idx = jnp.asarray(np.asarray(
            BT.pad_ints(slots, pad, slots[0]), np.int32))
        if self._slab is None:
            self._slab = MA.empty_slab(self.max_cached, self.m)
        self._slab = self._fill_fn()(self._slab, vs, idx)

    def context(self, v: int) -> MA.BarrettContext:
        """Device-resident Barrett context of v, from its slot (computed
        into one on a miss, evicting the least recently used).

        Thread-safe: one lock covers lookup, precompute dispatch, slot
        assignment and eviction, so concurrent calls cannot precompute
        a modulus twice or hand one slot to two moduli.  It is held
        while the precompute is dispatched, not while it runs."""
        self.check_modulus(v)
        slab, (slot,) = self._slots("barrett", [v])
        # the slot is read on the host: no device program per call site
        return MA.BarrettContext(*(jnp.asarray(np.asarray(s)[slot, ...])
                                   for s in slab))

    # -- compiled per-bucket executables ----------------------------------

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
                f = partial(MA.reduce_rows, impl=impl)
                widths = (2 * self.m,)
            elif op == "modmul":
                f = partial(MA.modmul_rows, impl=impl)
                widths = (self.m, self.m)
            elif op == "modexp":
                f = partial(MA.modexp_rows, impl=impl,
                            window_bits=self.window_bits)
                widths = (self.m, self.e_limbs)
            else:
                raise ValueError(op)
            self._fill_fn()         # the precompute its calls can need
            if self.capture_profiles:
                zs = [jnp.zeros((bucket, w), jnp.uint32) for w in widths]
                with self.telemetry.phase(op, "profile"):
                    self.static_profiles.setdefault(bucket, {})[op] = \
                        JS.trace_profile(
                            f, MA.empty_slab(self.max_cached, self.m),
                            jnp.zeros((bucket,), jnp.int32), *zs)
            # the slab is replicated; slot indices and rows are batched
            return BT.sharded_jit(f, self.mesh,
                                  range(1, 2 + len(widths)),
                                  n_args=2 + len(widths), n_out=1)
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
        """Full request validation (types, ranges, column lengths, the
        modulus or one modulus per row); returns the request length.
        Raises serving.errors InvalidRequest subtypes carrying the
        offending index."""
        schema = self._op_schema(op)
        if len(columns) != len(schema):
            raise E.InvalidRequest(
                f"{op} takes {len(schema)} columns, got {len(columns)}")
        with self.telemetry.phase(op, "validate"):
            n = E.check_lengths(columns, names=[s[0] for s in schema])
            for col, (name, lim, what) in zip(columns, schema):
                E.check_operands(name, col, lim, what)
            if isinstance(v, (list, tuple)):
                if len(v) != n:
                    raise E.InvalidRequest(
                        f"mismatched request columns: len({schema[0][0]})"
                        f" = {n}, len(v) = {len(v)}")
                for i, vi in enumerate(v):
                    self.check_modulus(vi, f"v[{i}]")
            elif v is not None:
                self.check_modulus(v)
        return n

    def _pieces(self, moduli: list[int]):
        """[(rows, bucket)]: the Batcher's chunks, each split into parts
        of at most `max_cached_moduli` distinct moduli where it holds
        more, so that every part's contexts fit the slab together."""
        out = []
        for lo, hi, bucket in self.batcher.plan(len(moduli)):
            by_mod: dict[int, list[int]] = {}
            for i in range(lo, hi):
                by_mod.setdefault(moduli[i], []).append(i)
            if len(by_mod) <= self.max_cached:
                out.append((list(range(lo, hi)), bucket))
                continue
            groups = list(by_mod.values())
            for g in range(0, len(groups), self.max_cached):
                rows = sorted(i for grp in groups[g:g + self.max_cached]
                              for i in grp)
                out.append((rows, self.batcher.bucket_for(len(rows))))
        return out

    def _run(self, op: str, v, columns, widths, *,
             impl: str | None = None) -> list[int]:
        """Pack int columns to limb batches, run per bucket, unpack.

        `v` is one modulus for every row or one per row.  `impl`
        overrides the service impl for this call (the serving
        frontend's degradation ladder; bit-identical by contract)."""
        n = self.validate(op, columns, v)
        if n == 0:
            return []
        self.telemetry.record_request(op, n)
        moduli = list(v) if isinstance(v, (list, tuple)) else [v] * n
        out: list[int] = [0] * n
        for rows, bucket in self._pieces(moduli):
            eff = BT.resolve_impl(impl or self.impl)
            self._fire("transfer", op=op, bucket=bucket)
            part = [moduli[i] for i in rows]
            with self.telemetry.phase(op, "contexts"):
                slab, slots = self._slots(op, part)
            self._call_moduli.observe(len(set(part)))
            with self.telemetry.phase(op, "pack"):
                idx = jnp.asarray(np.asarray(
                    BT.pad_ints(slots, bucket, slots[0]), np.int32))
                arrs = [jnp.asarray(bi.batch_from_ints(
                            BT.pad_ints([col[i] for i in rows], bucket, 0),
                            w))
                        for col, w in zip(columns, widths)]
            fn = self._fn(op, bucket, impl)
            self.telemetry.record_rows(bucket, len(rows))
            with self.telemetry.chunk_timer(op, bucket):
                self._fire("execute", op=op, bucket=bucket, impl=eff)
                res = np.asarray(fn(slab, idx, *arrs))
            with self.telemetry.phase(op, "unpack"):
                for i, r in zip(rows, bi.batch_to_ints(res[:len(rows)])):
                    out[i] = r
        return out

    # -- public entry points ----------------------------------------------

    def reduce(self, xs: list[int], v, *,
               impl: str | None = None) -> list[int]:
        """[x_i mod v_i] for double-width x (x < B^(2 m_limbs)); v is
        one modulus or one per row."""
        return self._run("reduce", v, [xs], [2 * self.m], impl=impl)

    def modmul(self, a: list[int], b: list[int], v, *,
               impl: str | None = None) -> list[int]:
        """[(a_i * b_i) mod v_i] for a_i, b_i < B^m_limbs."""
        return self._run("modmul", v, [a, b], [self.m, self.m],
                         impl=impl)

    def modexp(self, a: list[int], e: list[int], v, *,
               impl: str | None = None) -> list[int]:
        """[pow(a_i, e_i, v_i)] -- fixed-window ladder on each row's
        cached context."""
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
            "slots": self.max_cached,
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
