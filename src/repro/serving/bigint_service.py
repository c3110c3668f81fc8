"""Batched multi-precision division service -- the serving driver for
the paper's workload (many independent divisions at one precision).

Requests are Python ints; the service packs them into fixed-width limb
batches, pads the batch to the compiled batch size, runs the jitted
vmapped divmod (sharded across all available devices when a mesh is
given), and unpacks exact results.  One compiled executable per
(m_limbs, batch_bucket).  Bucket planning, padding, and mesh sharding
live in `serving.batching`, shared with `ModArithService`.

Observability (docs/observability.md): every bucket compile captures a
STATIC structural profile off the traced program -- Pallas launches,
XLA glue eqns, total eqns (`utils/jaxpr_stats.trace_profile`) plus the
`KernelPlan` -- and every request records runtime counters (requests,
true-vs-padded rows, per-bucket latency) on a per-instance registry,
and each host phase of a call (validate, pack, execute, unpack) as a
`service.<phase>` span on the profiler's clock.
`snapshot()` merges both; `obs/report.py` renders it as a
measured-vs-model table against the 2*iters + 1 launch contract.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax.numpy as jnp

from repro.core import bigint as bi
from repro.core import shinv as S
from repro.utils import jaxpr_stats as JS
from . import batching as BT
from . import errors as E


class BigintDivisionService:
    def __init__(self, m_limbs: int, mesh=None, impl: str | None = None,
                 batch_buckets=(64, 256, 1024),
                 capture_profiles: bool = True, faults=None):
        self.m = m_limbs
        self.mesh = mesh
        self.impl = impl
        self.capture_profiles = capture_profiles
        self.batcher = BT.Batcher(batch_buckets)
        self._fns = BT.CompiledBuckets()
        # per-bucket kernel geometry, recorded when the bucket compiles
        self.kernel_plans: dict[int, BT.KernelPlan] = {}
        # per-bucket static structural profiles, captured at the same
        # moment (a CompiledBuckets miss)
        self.static_profiles: dict[int, dict] = {}
        self.telemetry = BT.ServiceMetrics()
        self.faults = faults            # serving/faults.FaultInjector

    @property
    def buckets(self):
        return list(self.batcher.buckets)

    def set_fault_injector(self, faults) -> None:
        """Install (or clear, with None) a fault injector; the
        injection sites below are exact no-ops without one."""
        self.faults = faults

    def _fire(self, site: str, **labels) -> None:
        if self.faults is not None:
            self.faults.fire(site, **labels)

    def validate(self, op: str, columns, v=None) -> int:
        """Full request validation (types, ranges, column lengths);
        returns the request length.  Raises serving.errors
        InvalidRequest subtypes carrying the offending index."""
        if op != "divmod":
            raise E.InvalidRequest(f"unknown op {op!r} for "
                                   "BigintDivisionService")
        with self.telemetry.phase(op, "validate"):
            n = E.check_lengths(columns, names=("us", "vs"))
            lim = bi.BASE ** self.m
            E.check_operands("u", columns[0], lim, f"B^{self.m}")
            E.check_operands("v", columns[1], lim, f"B^{self.m}")
        return n

    def _fn(self, bucket: int, impl: str | None = None):
        eff = BT.resolve_impl(impl or self.impl)

        def build():
            self._fire("compile", op="divmod", bucket=bucket, impl=eff)
            # plan against the widest internal product: divmod pads to
            # m + PAD limbs and forms the double-width u * shinv there
            plan = BT.kernel_plan(BT.device_rows(bucket, self.mesh),
                                  self.m + S.PAD, eff)
            req = BT.resolve_impl(self.impl)
            if eff != req:
                plan = plan._replace(degraded_from=req)
            self.kernel_plans[bucket] = plan
            fn = partial(S.divmod_batch, impl=plan.impl)
            if self.capture_profiles:
                z = jnp.zeros((bucket, self.m), jnp.uint32)
                with self.telemetry.phase("divmod", "profile"):
                    self.static_profiles[bucket] = {
                        "divmod": JS.trace_profile(fn, z, z)}
            return BT.sharded_jit(fn, self.mesh,
                                  batched_argnums=(0, 1), n_args=2,
                                  n_out=2)
        return self._fns.get(("divmod", bucket, eff), build)

    def profile_bucket(self, bucket: int) -> dict:
        """Force-compile one bucket (trace only, no execution) and
        return its static structural profile."""
        self._fn(bucket)
        return self.static_profiles.get(bucket, {})

    def divide(self, us: list[int], vs: list[int], *,
               impl: str | None = None):
        """Exact (q, r) lists for batched u/v (v > 0; v = 0 follows
        the documented total extension (q, r) = (0, u)).

        `impl` overrides the service impl for this call -- the
        serving frontend's degradation ladder uses it to route a
        request down `kernels/ops.py:fallback_chain` when a kernel is
        quarantined (every impl is bit-identical, so the override
        never changes results)."""
        n = self.validate("divmod", (us, vs))
        if n == 0:
            return [], []
        self.telemetry.record_request("divmod", n)
        qs, rs = [], []
        for lo, hi, bucket in self.batcher.plan(n):
            eff = BT.resolve_impl(impl or self.impl)
            self._fire("transfer", op="divmod", bucket=bucket)
            with self.telemetry.phase("divmod", "pack"):
                u_pad = BT.pad_ints(us[lo:hi], bucket, 0)
                v_pad = BT.pad_ints(vs[lo:hi], bucket, 1)
                ua = jnp.asarray(bi.batch_from_ints(u_pad, self.m))
                va = jnp.asarray(bi.batch_from_ints(v_pad, self.m))
            fn = self._fn(bucket, impl)
            self.telemetry.record_rows(bucket, hi - lo)
            with self.telemetry.chunk_timer("divmod", bucket):
                self._fire("execute", op="divmod", bucket=bucket,
                           impl=eff)
                q, r = fn(ua, va)
                q, r = np.asarray(q), np.asarray(r)
            with self.telemetry.phase("divmod", "unpack"):
                keep = hi - lo
                qs += bi.batch_to_ints(q[:keep])
                rs += bi.batch_to_ints(r[:keep])
        return qs, rs

    # -- introspection ----------------------------------------------------

    def stats(self) -> dict:
        """Runtime counters only (see `snapshot` for the merged view)."""
        out = self.telemetry.stats()
        out["bucket_compiles"] = self._fns.misses
        out["bucket_reuses"] = self._fns.hits
        return out

    def snapshot(self) -> dict:
        """Merged static + runtime profile of the service: per-bucket
        KernelPlan geometry and structural trace counts alongside the
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
            "service": "bigint_division",
            "m_limbs": self.m,
            "impl": self.impl or K.default_impl(),
            "iters": S.refine_iters(self.m),
            "buckets": buckets,
            "runtime": self.stats(),
        }
