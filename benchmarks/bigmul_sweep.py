"""Batched multiplication sweep: natively batched Pallas kernel vs
vmap(mul_pallas) vs the blocked einsum, across precision x batch.

This records the perf trajectory toward the paper's target range
(2^15 - 2^18 bit operands; `--full`).  For each (bits, batch, impl)
cell it measures best-of-N wall time of one batched full product and
derives throughput (products/s) plus the operand-staging memory
footprint:

  * pallas_vmap      -- the single-instance kernel under jax.vmap;
                        pays a host-side (batch, nv, t, 2t) Toeplitz
                        gather, a ~2t-times blowup of the operand.
  * pallas_batched   -- batch as leading grid axis, Toeplitz tiles
                        staged in VMEM inside the kernel, carry
                        pre-resolution fused into the epilogue.  Peak
                        staging is block_b * t * 2t * 4 bytes,
                        independent of batch and precision.
  * blocked          -- pair-list einsum in plain XLA (CPU baseline).

Results append to BENCH_bigmul.json deterministically: rows are keyed
by (bits, batch, impl), re-runs update their keys in place, the file
is rewritten sorted with a stable schema, so diffs show only measured
numbers.  `--smoke` runs tiny sizes with exactness asserts -- the CI
tier-1 kernel-path regression gate.

Usage:
  PYTHONPATH=src python benchmarks/bigmul_sweep.py            # dev sizes
  PYTHONPATH=src python benchmarks/bigmul_sweep.py --smoke    # CI gate
  PYTHONPATH=src python benchmarks/bigmul_sweep.py --full     # 2^15..2^18
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bigint as bi
from repro.kernels import ops as K
from repro.kernels import bigmul
from repro.obs import costmodel as CM
from repro.obs import report as RPT
from repro.utils import jaxpr_stats as JS
from repro.utils import compile_cache

IMPLS = ("pallas_batched", "pallas_vmap", "blocked")

_SCHEMA = 2   # bump when row fields change (2: launches/model_launches)


def _bench(fn, *args, reps=3):
    out = jax.block_until_ready(fn(*args))   # compile + warmup
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts), out


def _make_batch(rng, m, batch):
    xs = [bi._rand_big(rng, bi.BASE ** (m - 1), bi.BASE ** m)
          for _ in range(batch)]
    ys = [bi._rand_big(rng, bi.BASE ** (m - 1), bi.BASE ** m)
          for _ in range(batch)]
    return (jnp.asarray(bi.batch_from_ints(xs, m)),
            jnp.asarray(bi.batch_from_ints(ys, m)), xs, ys)


def _runner(impl, out_width):
    if impl == "pallas_vmap":
        return jax.jit(jax.vmap(
            lambda a, b: bigmul.mul_pallas(a, b, out_width)))
    return jax.jit(lambda a, b: K.mul_batch(a, b, out_width, impl=impl))


def _staging_bytes(impl, m, batch):
    """Operand-staging footprint of the Toeplitz tiles (bytes)."""
    t = K.BLOCK_T
    nv = max(-(-2 * m // t), 1)
    if impl == "pallas_batched":
        return bigmul.pick_block_b(batch) * t * 2 * t * 4   # in-VMEM, per step
    # pallas_vmap and blocked both materialize the full batched
    # (batch, nv, t, 2t) Toeplitz gather in XLA before consuming it
    return batch * nv * t * 2 * t * 4


def run(log2bits, batches, impls, reps=3, validate=True, out_path=None):
    rng = np.random.default_rng(0)
    rows = []
    for lb in log2bits:
        bits = 1 << lb
        m = bi.width_for_bits(bits)
        wo = 2 * m
        for batch in batches:
            u, v, xs, ys = _make_batch(rng, m, batch)
            for impl in impls:
                fn = _runner(impl, wo)
                # structural telemetry off the traced program: launches
                # of one batched product vs the cost model's prediction
                # (pallas_vmap is registry impl "pallas" under jax.vmap)
                launches, xla_ops = JS.trace_counts(fn, u, v)
                model = CM.mul_launches(
                    "pallas" if impl == "pallas_vmap" else impl)
                dt, out = _bench(fn, u, v, reps=reps)
                ok = True
                if validate:
                    got = bi.batch_to_ints(np.asarray(out))
                    ok = all(g == x * y for g, x, y in zip(got, xs, ys))
                rows.append({
                    "bits": bits, "batch": batch, "impl": impl,
                    "ms": round(dt * 1e3, 3),
                    "products_per_s": round(batch / dt, 2),
                    "staging_bytes": _staging_bytes(impl, m, batch),
                    "launches": launches,
                    "xla_ops": xla_ops,
                    "model_launches": model,
                    "launch_match": launches == model,
                    "exact": ok,
                    "backend": jax.default_backend(),
                    "schema": _SCHEMA,
                })
                print(f"bits=2^{lb} batch={batch:4d} {impl:15s} "
                      f"{dt * 1e3:10.1f} ms  {batch / dt:10.2f} prod/s  "
                      f"staging={rows[-1]['staging_bytes']:>12d} B  "
                      f"exact={ok}", flush=True)
                if out_path:            # survive partial/killed runs
                    merge_json(out_path, rows)
    return rows


# Deterministic keyed merge, shared with every BENCH_*.json emitter
# (one row per (bits, batch, impl), updated field-wise, rewritten
# sorted; validated by tools/check_bench.py).  table1_div.py imports
# this name too.
merge_json = RPT.merge_json


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--log2bits", type=int, nargs="+",
                    default=[12, 13, 14],
                    help="operand sizes as log2(bits)")
    ap.add_argument("--batches", type=int, nargs="+", default=[16, 64])
    ap.add_argument("--impls", nargs="+", default=list(IMPLS),
                    choices=list(IMPLS))
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..",
        "BENCH_bigmul.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + exactness asserts (CI gate)")
    ap.add_argument("--full", action="store_true",
                    help="paper range: 2^15..2^18-bit operands")
    ap.add_argument("--no-validate", dest="validate", action="store_false")
    args = ap.parse_args(argv)

    if args.smoke:
        # exactness gate only: don't let a gate run on a slow/contended
        # machine overwrite the canonical timings in BENCH_bigmul.json
        args.log2bits, args.batches, args.reps = [10, 11], [4], 1
    elif args.full:
        args.log2bits = [15, 16, 17, 18]
    out_path = None if args.smoke else os.path.normpath(args.out)
    rows = run(args.log2bits, args.batches, args.impls,
               reps=args.reps, validate=args.validate, out_path=out_path)
    if not all(r["exact"] for r in rows):
        raise SystemExit("exactness check FAILED")
    if not all(r["launch_match"] for r in rows):
        raise SystemExit("launch count vs cost model FAILED")
    if out_path:
        print(f"wrote {out_path} ({len(rows)} rows updated)")
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    main()
