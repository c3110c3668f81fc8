"""Backend-independent launch/op accounting over traced jaxprs.

The fusion work (kernels/fused.py) is judged by a STRUCTURAL metric --
how many kernel launches and full-width XLA ops one division step
issues -- which, unlike wall time, is meaningful on any backend
(including the CPU interpret mode CI runs in).  These helpers walk a
ClosedJaxpr recursively (through pjit / scan / cond / custom_vmap
sub-jaxprs) and count primitives, so benchmarks/div_breakdown.py,
tests/test_fused.py, and the serving static profiles
(obs/telemetry.py) can assert "one Refine iteration == 2 Pallas
launches" directly on the traced program.

Counting semantics (pinned by tests/test_jaxpr_stats.py):

  * `pallas_launches` counts pallas_call eqns at the XLA level only
    (into_kernels=False): a kernel's body executes inside the launch,
    so anything reachable from it -- including sub-jaxprs the body
    stages for `pl.when`/loops -- must never be counted again.
    Nested pjit-of-pallas_call counts ONE launch regardless of
    wrapper depth; a custom_vmap'd kernel counts ONE whether traced
    batched (the rule) or unbatched (the `call` jaxpr); an empty
    jaxpr counts zero.
  * Counts are STATIC: a pallas_call inside a `scan`/`while` body is
    counted once, though it re-launches per trip at runtime.  Use
    `runtime_pallas_launches` when the per-execution number is the
    quantity of interest (e.g. a modexp ladder, whose launches all sit
    inside scan bodies); it weights scan bodies by their static
    `length` (while-loop trip counts are unknowable statically and
    count once, documented lower bound).
  * Both `cond` branches are walked: the static count is the upper
    bound over branches, matching what is compiled, not what one
    execution dispatches.
"""

from __future__ import annotations

from collections import Counter

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr


def _sub_jaxprs(params):
    """Yield every (Closed)Jaxpr reachable from an eqn's params."""
    for val in params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, Jaxpr):
                yield v


def iter_eqns(jaxpr, into_kernels: bool = True):
    """Depth-first iteration over all eqns, including nested jaxprs.

    into_kernels=False stops at pallas_call boundaries: the kernel eqn
    itself IS yielded (it is one launch), but none of the jaxprs in
    its params are walked -- the kernel body and any sub-jaxprs it
    stages execute inside the kernel, not as XLA ops, so yielding
    them would double-count in-kernel work as dispatches.  Every
    other eqn is yielded AND has its param jaxprs walked (pjit, scan,
    cond, custom_vmap, remat, ...).  An empty jaxpr yields nothing.
    """
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        if not into_kernels and eqn.primitive.name == "pallas_call":
            continue
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, into_kernels)


def primitive_counts(jaxpr) -> Counter:
    """Counter of primitive names over the whole (nested) jaxpr."""
    return Counter(eqn.primitive.name for eqn in iter_eqns(jaxpr))


def count_primitive(jaxpr, name: str) -> int:
    return sum(1 for eqn in iter_eqns(jaxpr) if eqn.primitive.name == name)


def pallas_launches(jaxpr) -> int:
    """STATIC number of Pallas kernel launches in the traced program.

    Counted with into_kernels=False: a pallas_call is one launch no
    matter how deeply pjit/custom_vmap wrapping nests it, and nothing
    inside a kernel body can ever be counted as a second launch.
    Scan bodies count once (see `runtime_pallas_launches` for the
    trip-weighted number); cond counts every branch."""
    return sum(1 for eqn in iter_eqns(jaxpr, into_kernels=False)
               if eqn.primitive.name == "pallas_call")


def runtime_pallas_launches(jaxpr) -> int:
    """Per-execution Pallas launch count: like `pallas_launches`, but
    a launch inside a `scan` body counts `length` times (nested scans
    multiply).  This is the number a device actually dispatches for
    ladder-style programs (modexp: every launch sits inside a scan),
    and what the cost model's `obs/costmodel.py:modexp_launches`
    predicts.  While-loop bodies count once (static lower bound);
    cond still counts every branch (upper bound)."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
            continue
        weight = (eqn.params["length"]
                  if eqn.primitive.name == "scan" else 1)
        total += weight * sum(runtime_pallas_launches(sub)
                              for sub in _sub_jaxprs(eqn.params))
    return total


def total_eqns(jaxpr) -> int:
    """Total primitive count including in-kernel bodies."""
    return sum(1 for _ in iter_eqns(jaxpr))


def xla_eqns(jaxpr) -> int:
    """Primitive count OUTSIDE kernel bodies: a proxy for XLA op
    dispatches (the glue the fusion removes).  Each pallas_call counts
    as one."""
    return sum(1 for _ in iter_eqns(jaxpr, into_kernels=False))


def trace_counts(fn, *args, **kwargs):
    """(pallas_launches, xla_eqns) of fn traced on the given args."""
    jx = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return pallas_launches(jx), xla_eqns(jx)


def trace_profile(fn, *args, **kwargs) -> dict:
    """Full structural profile of fn traced on the given args: the
    static-profile record the serving layer stores per compiled bucket
    (see docs/observability.md for the schema)."""
    jx = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return {
        "pallas_launches": pallas_launches(jx),
        "runtime_pallas_launches": runtime_pallas_launches(jx),
        "xla_eqns": xla_eqns(jx),
        "total_eqns": total_eqns(jx),
    }
