"""Observability subsystem: metrics, cost model, and reporting.

Three deliberately small modules:

  telemetry   dependency-free counters/gauges/histograms with labeled
              series, JSON / line-protocol export, and `span`: a
              host span on the profiler's clock that can also time
              into a histogram, always on.
  costmodel   the paper's multiplication/launch cost model as ONE
              importable source of truth -- `kernels/fused.py` and
              `serving/batching.kernel_plan` re-export their
              accounting constants from here, so the model the
              comparator predicts against can never drift from the
              numbers the kernels claim.
  report      measured-vs-model tables (the repo's own "Table 1"
              discipline) rendered from service snapshots, plus the
              shared keyed-merge JSON schema all BENCH_*.json
              benchmark emitters use.

Nothing in this package imports jax at module scope: the registry is
host-side state recorded OUTSIDE jit boundaries (structural facts are
captured once at trace/compile time), so no global mutable singleton
can leak into traced code.
"""

from . import costmodel, report, telemetry  # noqa: F401
