"""Per-stage division breakdown: fused vs unfused `divmod_batch`.

For each (bits, batch, impl) cell this measures where a batched
division spends its time -- the Newton refinement (`shinv_batch`) vs
the finalization (total - shinv) -- and, more importantly, reports the
STRUCTURAL fusion metrics straight off the traced program
(repro.utils.jaxpr_stats):

  launches          Pallas kernel launches in one divmod_batch
  launches_per_iter launches of the refinement / iteration count
                    (<= 2 for impl="pallas_fused" -- the paper's
                    one-kernel-per-step fusion; ~2 mul launches PLUS
                    ~15 XLA glue ops for the unfused composition)
  xla_ops           primitive dispatches outside kernel bodies (the
                    glue the fusion removes from the hot loop)
  model_launches    the cost model's prediction for the same cell
                    (repro.obs.costmodel: 2i+1 fused, 2i+2 unfused
                    pallas, 0 pure-XLA) -- `launch_match` records
                    measured == model, so BENCH_div.json carries the
                    measured-vs-model verdict per row

Wall times are backend-honest: on CPU the fused kernels execute in
Pallas interpret mode (validation, not speed -- the speedup claim is
for compiled TPU launches, where every avoided launch is an HBM round
trip; the launch/op counts above are the backend-independent
evidence).  Rows merge deterministically into BENCH_div.json keyed by
(bits, batch, impl); re-runs update in place, the file stays sorted.

For impl="pallas_fused" each row also records which fused-kernel
GENERATION the size dispatches to (`fused_path`: "unrolled" below the
VMEM/compile threshold, "grid" above -- see kernels/ops.fused_path)
and, on the grid path, the phase-tape geometry of the finalization
kernel (grid_steps, super_tile, revisit_passes from fused.grid_plan).

Usage:
  PYTHONPATH=src python benchmarks/div_breakdown.py            # dev sizes
  PYTHONPATH=src python benchmarks/div_breakdown.py --smoke    # CI gate
  PYTHONPATH=src python benchmarks/div_breakdown.py --counts-only \
      --log2bits 8 9 10 11 12 13 14 15   # structural sweep, no execution
  PYTHONPATH=src python benchmarks/div_breakdown.py --paper-range \
      # the paper's 2^15..2^18-bit Table 1 range: structural sweep of
      # the grid-scheduled fused path, merged into BENCH_div.json
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bigint as bi
from repro.core import shinv as S
from repro.obs import costmodel as CM
from repro.obs import report as RPT
from repro.utils import jaxpr_stats as JS
from repro.utils import compile_cache

IMPLS = ("pallas_fused", "pallas_batched", "blocked")

_SCHEMA = 2   # bump when row fields change (2: model_launches/launch_match)


def _bench(fn, *args, reps=3):
    out = jax.block_until_ready(fn(*args))   # compile + warmup
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts), out


def _make_batch(rng, m, batch):
    """Dividends at full precision, divisors at half (the regime where
    the refinement actually iterates)."""
    us = [bi._rand_big(rng, bi.BASE ** (m - 1), bi.BASE ** m)
          for _ in range(batch)]
    vs = [bi._rand_big(rng, bi.BASE ** (m // 2 - 1), bi.BASE ** (m // 2))
          for _ in range(batch)]
    return (jnp.asarray(bi.batch_from_ints(us, m)),
            jnp.asarray(bi.batch_from_ints(vs, m)), us, vs)


def iters_for(m: int) -> int:
    return S.refine_iters(m)     # single source of truth: core/shinv.py


def structural_counts(m: int, batch: int, impl: str, windowed: bool = True):
    """(launches, launches_per_iter, xla_ops) for divmod_batch traced
    at (batch, m) -- no compilation or execution."""
    u = jnp.zeros((batch, m), jnp.uint32)
    v = jnp.zeros((batch, m), jnp.uint32)
    launches, xla_ops = JS.trace_counts(
        lambda a, b: S.divmod_batch(a, b, impl=impl, windowed=windowed),
        u, v)
    it = iters_for(m)
    w = m + S.PAD
    sh_launches, _ = JS.trace_counts(
        lambda a, b: S.shinv_batch(a, b, iters_max=it, impl=impl,
                                   windowed=windowed),
        jnp.zeros((batch, w), jnp.uint32), jnp.zeros((batch,), jnp.int32))
    return launches, sh_launches / it, xla_ops


def fused_geometry(m: int) -> dict:
    """Which fused-kernel generation an m-limb division dispatches to,
    plus the grid phase-tape geometry of its finalization kernel."""
    from repro.kernels import fused as F
    w = m + S.PAD
    path = F.correct_dispatch(w)[0]
    out = {"fused_path": path}
    if path == "grid":
        steps, s_tile, passes = F.grid_plan(w)
        out.update({"grid_steps": steps, "super_tile": s_tile,
                    "revisit_passes": passes})
    return out


def run(log2bits, batches, impls, reps=3, validate=True, out_path=None,
        counts_only=False):
    rng = np.random.default_rng(0)
    rows = []
    for lb in log2bits:
        bits = 1 << lb
        m = bi.width_for_bits(bits)
        it = iters_for(m)
        for batch in batches:
            u, v, us, vs = (None, None, None, None)
            if not counts_only:
                u, v, us, vs = _make_batch(rng, m, batch)
            for impl in impls:
                launches, lpi, xla_ops = structural_counts(m, batch, impl)
                model = CM.divmod_launches(m, impl)
                row = {
                    "bits": bits, "batch": batch, "impl": impl,
                    "iters": it,
                    "launches": launches,
                    "launches_per_iter": round(lpi, 2),
                    "xla_ops": xla_ops,
                    # the paper cost model's launch prediction for this
                    # impl (obs/costmodel.py) next to the measurement
                    "model_launches": model,
                    "launch_match": launches == model,
                    "backend": jax.default_backend(),
                    "schema": _SCHEMA,
                }
                if impl == "pallas_fused":
                    row.update(fused_geometry(m))
                if not counts_only:
                    total_fn = jax.jit(lambda a, b, i=impl: S.divmod_batch(
                        a, b, impl=i))
                    dt, (q, r) = _bench(total_fn, u, v, reps=reps)
                    w = m + S.PAD
                    vw = jnp.zeros((batch, w), jnp.uint32
                                   ).at[:, :m].set(v)
                    # h = prec(u): significant limb count of each dividend
                    h = jnp.asarray([-(-x.bit_length() // bi.LOG_BASE)
                                     for x in us], jnp.int32)
                    sh_fn = jax.jit(lambda a, b, i=impl: S.shinv_batch(
                        a, b, iters_max=it, impl=i))
                    dt_sh, _ = _bench(sh_fn, vw, h, reps=reps)
                    ok = True
                    if validate:
                        qs = bi.batch_to_ints(np.asarray(q))
                        rs = bi.batch_to_ints(np.asarray(r))
                        ok = all((qq, rr) == divmod(x, y) for x, y, qq, rr
                                 in zip(us, vs, qs, rs))
                    row.update({
                        "total_ms": round(dt * 1e3, 3),
                        "shinv_ms": round(dt_sh * 1e3, 3),
                        "correct_ms": round(max(dt - dt_sh, 0.0) * 1e3, 3),
                        "divisions_per_s": round(batch / dt, 2),
                        "exact": ok,
                    })
                rows.append(row)
                msg = (f"bits=2^{lb} batch={batch:4d} {impl:15s} "
                       f"launches={launches:3d} "
                       f"({row['launches_per_iter']:.1f}/iter) "
                       f"xla_ops={xla_ops:5d}")
                if "fused_path" in row:
                    msg += f"  path={row['fused_path']}"
                    if row["fused_path"] == "grid":
                        msg += (f" (tape={row['grid_steps']} "
                                f"tile={row['super_tile']})")
                if not counts_only:
                    msg += (f"  total={row['total_ms']:10.1f} ms "
                            f"(shinv {row['shinv_ms']:.1f})"
                            f"  exact={row['exact']}")
                print(msg, flush=True)
                if out_path:            # survive partial/killed runs
                    merge_json(out_path, rows)
    return rows


# Deterministic keyed merge (one row per (bits, batch, impl), updated
# field-wise, rewritten sorted).  The writer now lives with the shared
# benchmark schema in repro.obs.report; `tools/check_bench.py`
# validates the invariants it maintains.
merge_json = RPT.merge_json


def _obs_smoke(m, batch, us, vs):
    """Observability gate: drive a BigintDivisionService end to end,
    then assert the snapshot's measured per-bucket launch counts equal
    the cost model's 2*iters + 1 prediction (obs/costmodel.py) and the
    runtime counters saw exactly this traffic."""
    from repro.serving.bigint_service import BigintDivisionService
    svc = BigintDivisionService(m_limbs=m, impl="pallas_fused",
                                batch_buckets=(batch,))
    qs, rs = svc.divide(us, vs)
    if not all((q, r) == divmod(x, y)
               for x, y, q, r in zip(us, vs, qs, rs)):
        raise SystemExit("obs: service exactness FAILED")
    snap = svc.snapshot()
    print(RPT.render_measured_vs_model(snap))
    want = 2 * iters_for(m) + 1
    for row in RPT.measured_vs_model(snap):
        if not row["match"]:
            raise SystemExit(
                f"obs: measured {row['measured_launches']} != model "
                f"{row['model_launches']} (bucket {row['bucket']})")
        if row["measured_launches"] != want:
            raise SystemExit(
                f"obs: launches {row['measured_launches']} != 2i+1={want}")
    rt = snap["runtime"]
    if rt["requests"].get("divmod", 0) != 1:
        raise SystemExit("obs: request counter FAILED")
    if rt["pad_waste"] != 0.0:     # batch == bucket: no padding
        raise SystemExit(f"obs: pad_waste {rt['pad_waste']} != 0")
    print(f"obs: snapshot launches == cost model ({want}), "
          f"counters consistent")


def _smoke(out_path):
    """CI gate: tiny sizes, exactness + bit-equivalence + the <= 2
    launches/iteration fusion contract, for BOTH fused-kernel
    generations (the grid-scheduled path is forced via the dispatch
    threshold override so it runs at smoke sizes), then the
    observability gate (`_obs_smoke`)."""
    from repro.kernels import ops as KO
    rng = np.random.default_rng(7)
    m, batch = 16, 4            # 256-bit operands
    u, v, us, vs = _make_batch(rng, m, batch)
    qb, rb = jax.block_until_ready(
        S.divmod_batch(u, v, impl="blocked"))
    for forced, label in ((None, "unrolled"), (1, "grid")):
        KO.set_fused_grid_threshold(forced)
        try:
            qf, rf = jax.block_until_ready(
                S.divmod_batch(u, v, impl="pallas_fused"))
            if not (np.array_equal(np.asarray(qf), np.asarray(qb))
                    and np.array_equal(np.asarray(rf), np.asarray(rb))):
                raise SystemExit(f"{label}: bit-equivalence FAILED")
            qs = bi.batch_to_ints(np.asarray(qf))
            rs = bi.batch_to_ints(np.asarray(rf))
            if not all((qq, rr) == divmod(x, y)
                       for x, y, qq, rr in zip(us, vs, qs, rs)):
                raise SystemExit(f"{label}: exactness check FAILED")
            launches, lpi, _ = structural_counts(m, batch, "pallas_fused")
            if lpi > 2:
                raise SystemExit(
                    f"{label}: fusion contract FAILED: {lpi} > 2/iter")
            if launches != 2 * iters_for(m) + 1:
                raise SystemExit(
                    f"{label}: unexpected launch count {launches}")
            print(f"smoke[{label}]: bit-equal, exact, "
                  f"{lpi:.1f} launches/iter (total {launches})")
        finally:
            KO.set_fused_grid_threshold(None)
    _obs_smoke(m, batch, us, vs)
    rows = run([8, 9], [batch], ["pallas_fused", "blocked"],
               counts_only=True, out_path=None)
    if not all(r["launch_match"] for r in rows):
        raise SystemExit("smoke: launch_match FAILED")
    print("smoke OK")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--log2bits", type=int, nargs="+", default=[8, 10, 12],
                    help="operand sizes as log2(bits)")
    ap.add_argument("--batches", type=int, nargs="+", default=[16])
    ap.add_argument("--impls", nargs="+", default=list(IMPLS),
                    choices=list(IMPLS))
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..",
        "BENCH_div.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + exactness/fusion asserts (CI gate)")
    ap.add_argument("--counts-only", action="store_true",
                    help="structural launch/op counts only (trace, no "
                         "execution -- fast at any precision)")
    ap.add_argument("--paper-range", action="store_true",
                    help="the paper's 2^15..2^18-bit Table 1 range: "
                         "structural sweep of the grid-scheduled fused "
                         "path (implies --counts-only)")
    ap.add_argument("--no-validate", dest="validate", action="store_false")
    args = ap.parse_args(argv)

    if args.smoke:
        return _smoke(os.path.normpath(args.out))
    if args.paper_range:
        args.log2bits = [15, 16, 17, 18]
        args.impls = ["pallas_fused"]
        args.counts_only = True

    out_path = os.path.normpath(args.out)
    rows = run(args.log2bits, args.batches, args.impls, reps=args.reps,
               validate=args.validate, out_path=out_path,
               counts_only=args.counts_only)
    if not all(r.get("exact", True) for r in rows):
        raise SystemExit("exactness check FAILED")
    print(f"wrote {out_path} ({len(rows)} rows updated)")
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    main()
