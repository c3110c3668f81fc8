"""Request-batching machinery shared by the serving frontends.

Both `BigintDivisionService` (division) and `ModArithService` (Barrett
modular arithmetic) follow the same pattern: requests arrive as Python
int lists of arbitrary length, get padded to one of a fixed set of
compiled batch-bucket sizes (one executable per bucket), optionally
sharded across a device mesh on the batch axis, and the results are
trimmed back to the true request size.  This module owns that pattern.

`kernel_plan` extends bucket planning down into the kernel: for each
(batch bucket, operand precision) pair it reports the multiplication
impl and the grid shape the natively batched Pallas kernel will launch
(instances per grid step x scheduled block pairs), mirroring
`kernels.bigmul.pick_block_b` / `_pair_schedule_pruned` so services
can record and expose their per-bucket kernel geometry.  For
impl="pallas_fused" the plan additionally records which fused-kernel
GENERATION the precision dispatches to (`grid_scheduled`, from
`kernels.ops.fused_path`) and, on the grid path, the phase-tape
geometry (`grid_steps`, `super_tile`, `revisit_passes`, from
`kernels.fused.grid_plan`) -- the knobs that bound VMEM and compile
time at the paper's 2^15..2^18-bit precisions.
"""

from __future__ import annotations

import threading
from typing import NamedTuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs import telemetry as T
from repro.utils import compat


def resolve_impl(impl: str | None) -> str:
    """Concrete impl name for an optional override (None = backend
    default), shared by the services and the frontend ladder."""
    from repro.kernels import ops as K
    return impl or K.default_impl()


class KernelPlan(NamedTuple):
    """Kernel geometry for one (bucket, precision) pair."""
    impl: str          # resolved multiplication impl
    block_b: int       # instances per grid step (1 unless batched pallas)
    grid_rows: int     # leading (batch) grid rows per launch
    grid_pairs: int    # scheduled (i, j) block pairs of the dominant
                       # full-width product at this precision
    fused: bool = False        # division glue executes in-kernel
    step_launches: int = 0     # kernel launches per Refine iteration
    step_glue_ops: int = 0     # full-width XLA glue ops per iteration
    grid_scheduled: bool = False  # fused pair axis on the Pallas grid
    grid_steps: int = 0        # phase-tape length of the finalization
                               # kernel (pair steps + revisit passes)
    super_tile: int = 0        # per-step product tile, in sub-digits
    revisit_passes: int = 0    # stage/glue revisit passes per launch
    degraded_from: str = ""    # non-empty when this bucket compiled a
                               # FALLBACK impl (serving degradation
                               # ladder) instead of the requested one


def kernel_plan(bucket: int, w_limbs: int,
                impl: str | None = None) -> KernelPlan:
    """Plan the kernel grid for `bucket` instances of `w_limbs`-limb
    operands (the service's widest internal product).

    Single source of truth is the kernel itself: block_b comes from
    `bigmul.pick_block_b`, the pair count from the same ceil-division
    blocking the kernel schedule uses, the fused-step geometry
    (launches vs XLA glue ops per Refine iteration) from the cost
    model (`repro.obs.costmodel`, which kernels/fused.py re-exports,
    so the plan can never drift from the measured-vs-model
    comparator), and the unrolled-vs-grid generation plus its
    phase-tape geometry from `ops.fused_path` / `fused.grid_plan`, so
    the plan is exactly what a launch at this (bucket, precision) will
    execute.
    """
    from repro.kernels import ops as K
    from repro.kernels import bigmul, fused
    from repro.obs import costmodel as CM
    impl = impl or K.default_impl()
    nb = max(-(-2 * w_limbs // K.BLOCK_T), 1)    # sub-digit blocks/operand
    if impl == "pallas_fused":
        bb = bigmul.pick_block_b(bucket)
        grid = fused.correct_dispatch(w_limbs)[0] == "grid"
        steps, s_tile, passes = (fused.grid_plan(w_limbs) if grid
                                 else (0, 0, 0))
        return KernelPlan(impl, bb, -(-bucket // bb), nb * nb,
                          fused=True,
                          step_launches=CM.step_launches(impl),
                          step_glue_ops=CM.step_glue_ops(impl),
                          grid_scheduled=grid, grid_steps=steps,
                          super_tile=s_tile, revisit_passes=passes)
    if impl == "pallas_batched":
        bb = bigmul.pick_block_b(bucket)
        return KernelPlan(impl, bb, -(-bucket // bb), nb * nb,
                          fused=False,
                          step_launches=CM.step_launches(impl),
                          step_glue_ops=CM.step_glue_ops(impl))
    # "pallas" still launches its 2 per-lane mul kernels each
    # iteration; "scan"/"blocked" run everything as XLA ops.
    return KernelPlan(impl, 1, bucket, nb * nb,
                      fused=False,
                      step_launches=CM.step_launches(impl),
                      step_glue_ops=CM.step_glue_ops(impl))


def device_rows(bucket: int, mesh) -> int:
    """Rows each device's kernels launch with: the whole bucket, or
    under a mesh (`sharded_jit`) an even share of it."""
    return bucket if mesh is None else bucket // mesh.size


class Batcher:
    """Plans how a request of size n maps onto compiled bucket sizes.

    Oversized requests are split into largest-bucket chunks; the final
    partial chunk gets the smallest bucket that fits it.
    """

    def __init__(self, buckets=(64, 256, 1024)):
        if not buckets:
            raise ValueError("need at least one batch bucket")
        self.buckets = tuple(sorted(buckets))

    def bucket_for(self, n: int) -> int:
        return next((b for b in self.buckets if b >= n), self.buckets[-1])

    def plan(self, n: int) -> list[tuple[int, int, int]]:
        """[(lo, hi, bucket)] chunks covering range(n); an empty
        request plans no chunks."""
        if n <= 0:
            return []
        big = self.buckets[-1]
        out, i = [], 0
        while n - i > big:
            out.append((i, i + big, big))
            i += big
        out.append((i, n, self.bucket_for(n - i)))
        return out


def pad_ints(xs, bucket: int, fill: int) -> list:
    """Pad a request column to the bucket size with a benign fill."""
    return list(xs) + [fill] * (bucket - len(xs))


def sharded_jit(fn, mesh, batched_argnums, n_args: int, n_out: int = 1):
    """jit `fn`; under a mesh, shard the batched args and all outputs on
    the batch axis and replicate the rest (e.g. a cached BarrettContext,
    which is a pytree -- the replicated spec applies to its leaves).

    XLA cannot partition a Pallas (Mosaic) kernel, so under a mesh `fn`
    runs inside a shard_map: every device computes its own rows (they
    are independent) and no collective is needed.  The batch must
    divide evenly over the mesh.
    """
    if mesh is None:
        return jax.jit(fn)
    row = P(tuple(mesh.axis_names))
    batched = set(batched_argnums)
    in_specs = tuple(row if i in batched else P() for i in range(n_args))
    out_specs = row if n_out == 1 else (row,) * n_out
    local = compat.shard_map(fn, mesh, in_specs, out_specs)
    rows = NamedSharding(mesh, row)
    return jax.jit(
        local,
        in_shardings=tuple(NamedSharding(mesh, s) for s in in_specs),
        out_shardings=rows if n_out == 1 else (rows,) * n_out)


class ServiceMetrics:
    """The service-standard runtime metric families, on one Registry.

    Shared by both serving frontends so their `stats()` dictionaries
    and exported series are uniform (docs/observability.md documents
    the names/labels).  All recording happens host-side around the
    compiled per-bucket calls -- nothing here touches traced values.
    Each phase of a call is a `telemetry.span` named `service.<phase>`
    (validate, pack, execute, unpack, precompute, profile), on the
    profiler's clock and in `phase_seconds{op, phase}`.
    """

    def __init__(self):
        self.registry = T.Registry()
        self._requests = self.registry.counter(
            "requests_total", "service endpoint calls", ("op",))
        self._items = self.registry.counter(
            "items_total", "true (unpadded) request rows", ("op",))
        self._rows_true = self.registry.counter(
            "batch_rows_true_total", "true rows per compiled bucket",
            ("bucket",))
        self._rows_padded = self.registry.counter(
            "batch_rows_padded_total", "bucket-padded rows submitted",
            ("bucket",))
        self._latency = self.registry.histogram(
            "bucket_seconds", "per-bucket execution wall time",
            ("op", "bucket"))
        self._phases = self.registry.histogram(
            "phase_seconds", "wall time of each host phase of a call",
            ("op", "phase"))

    def record_request(self, op: str, n_items: int) -> None:
        self._requests.labels(op=op).inc()
        self._items.labels(op=op).inc(n_items)

    def phase(self, op: str, phase: str):
        """The `service.<phase>` span, timed into `phase_seconds`."""
        return T.span(f"service.{phase}",
                      self._phases.labels(op=op, phase=phase))

    def chunk_timer(self, op: str, bucket: int):
        """The `service.execute` span of one padded-bucket execution
        (the compiled call and the copy of its result), timed into
        both `phase_seconds` and `bucket_seconds`."""
        return T.span("service.execute",
                      (self._phases.labels(op=op, phase="execute"),
                       self._latency.labels(op=op, bucket=bucket)))

    def record_rows(self, bucket: int, true_rows: int) -> None:
        self._rows_true.labels(bucket=bucket).inc(true_rows)
        self._rows_padded.labels(bucket=bucket).inc(bucket)

    def pad_waste(self) -> float:
        """Fraction of submitted rows that were padding: (padded -
        true) / padded over the service lifetime (0.0 when idle)."""
        padded = sum(s.value for s in self._rows_padded.series())
        true = sum(s.value for s in self._rows_true.series())
        return (padded - true) / padded if padded else 0.0

    def stats(self) -> dict:
        """Plain-data runtime counters (structural fields exact and
        deterministic; timing fields are wall-clock sums)."""
        return {
            "requests": {s.labels["op"]: int(s.value)
                         for s in self._requests.series()},
            "items": {s.labels["op"]: int(s.value)
                      for s in self._items.series()},
            "rows_true": int(sum(s.value
                                 for s in self._rows_true.series())),
            "rows_padded": int(sum(s.value
                                   for s in self._rows_padded.series())),
            "pad_waste": self.pad_waste(),
            "bucket_seconds": {
                f"{s.labels['op']}/b{s.labels['bucket']}":
                    {"count": s.count, "sum": s.value}
                for s in self._latency.series()},
        }


class CompiledBuckets:
    """Lazy cache of compiled executables, keyed by (op, bucket[,
    impl]).

    Tracks hits/misses so services can expose bucket-compile counts;
    `build` runs only on a miss, which is where the services capture
    each bucket's static structural profile (trace_profile + the
    KernelPlan) -- see serving/bigint_service.py and
    serving/modexp_service.py `snapshot()`.

    Thread-safe: concurrent requests against an uncompiled bucket must
    not double-compile it (two racing `build()`s waste minutes at
    large precisions) or corrupt the dict, so get() holds one RLock
    across the check-and-build.  This serializes first-touch compiles
    of DIFFERENT buckets too -- acceptable, since steady-state traffic
    is all hits and a failed build leaves nothing cached (the next
    request retries it)."""

    def __init__(self):
        self._fns: dict[object, object] = {}
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0

    def get(self, key, build):
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self.hits += 1
                return fn
            self.misses += 1
            fn = build()
            self._fns[key] = fn
            return fn

    def __len__(self) -> int:
        with self._lock:
            return len(self._fns)
