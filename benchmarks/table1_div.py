"""Table 1 analog: multiplication vs division throughput across
precisions (the paper's central evaluation).

The paper fixes Num Bits x Num Insts = 2^32 on an A100; on this CPU
container we keep the same *structure* (batched instances, prec(u) =
M-2, prec(v) uniform in [2, M/2] -- maximal Refine iterations) with
Num Bits x Num Insts = 2^24 so wall times stay in seconds.  Columns:

  bits, insts, mul_ms, div_ms, div/mul ratio, GMP-proxy (Python-int)
  speedup, and exactness check vs Python divmod.

The div/mul ratio is the paper's cost-model metric: Sec 2.3 predicts
[5, 7] full multiplications for the size-adaptive algorithm; the
fixed-shape JAX v1 executes every Refine iteration at full width, so
its ratio is higher -- the windowed variant (ops-level bucketing,
EXPERIMENTS.md SPerf) closes the gap toward the model.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bigint as bi
from repro.core import shinv as S
from repro.kernels import ops as K
from repro.obs import costmodel as CM
from repro.utils import compile_cache

BUDGET_BITS = 1 << 22          # Num Bits x Num Insts
MAX_INSTS = 256


def _bench(fn, *args, reps=3):
    fn(*args)                   # compile + warmup
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def make_dataset(rng, m, insts):
    us, vs = [], []
    for _ in range(insts):
        us.append(bi._rand_big(rng, bi.BASE ** (m - 3), bi.BASE ** (m - 2)))
        kv = int(rng.integers(2, m // 2 + 1))
        vs.append(bi._rand_big(rng, bi.BASE ** (kv - 1), bi.BASE ** kv))
    return (jnp.asarray(bi.batch_from_ints(us, m)),
            jnp.asarray(bi.batch_from_ints(vs, m)), us, vs)


def _db():
    """Import the sibling div_breakdown benchmark (shared structural
    counters and the deterministic JSON writer)."""
    import sys
    d = os.path.dirname(os.path.abspath(__file__))
    if d not in sys.path:
        sys.path.insert(0, d)
    import div_breakdown
    return div_breakdown


def run_counts(sizes, impl="pallas_fused", windowed=True):
    """Structural sweep (trace only, no execution): Pallas launches and
    XLA glue ops of one batched division per size, plus the fused
    generation dispatch (`fused_path`) and grid phase-tape geometry.
    This is how the paper's 2^15..2^18-bit range is characterized on
    backends where wall time would measure the interpreter."""
    DB = _db()
    rows = []
    for bits in sizes:
        m = bi.width_for_bits(bits)
        insts = min(max(BUDGET_BITS // bits, 4), MAX_INSTS)
        launches, lpi, xla_ops = DB.structural_counts(m, insts, impl,
                                                      windowed=windowed)
        model = CM.divmod_launches(m, impl)
        row = {"bits": bits, "insts": insts, "impl": impl,
               "windowed": windowed, "iters": S.refine_iters(m),
               "launches": launches, "launches_per_iter": round(lpi, 2),
               "xla_ops": xla_ops,
               "model_launches": model, "launch_match": launches == model}
        if impl == "pallas_fused":
            row.update(DB.fused_geometry(m))
        rows.append(row)
        print(f"bits={bits} insts={insts} {impl}: launches={launches} "
              f"({lpi:.1f}/iter) xla_ops={xla_ops} "
              f"{row.get('fused_path', '')}", flush=True)
    return rows


def run(sizes=(2 ** 10, 2 ** 12, 2 ** 14, 2 ** 16), validate=True,
        impl="blocked", windowed=True):
    """Per-size mul vs div timings.  `sizes` may extend to the paper's
    2^15..2^18-bit range (`--paper-range`); with impl="pallas_batched"
    the vmapped mul/div route whole batches to the natively batched
    kernel via the custom_vmap rule in kernels/ops.py."""
    rng = np.random.default_rng(0)
    rows = []
    for bits in sizes:
        m = bi.width_for_bits(bits)
        insts = min(max(BUDGET_BITS // bits, 4), MAX_INSTS)
        u, v, us, vs = make_dataset(rng, m, insts)

        mul = jax.jit(jax.vmap(
            lambda a, b: K.mul(a, b, 2 * m, impl=impl)))
        t_mul = _bench(mul, u, v)

        div = jax.jit(lambda a, b: S.divmod_batch(a, b, impl=impl,
                                                  windowed=windowed))
        t_div = _bench(div, u, v)

        # GMP proxy: Python ints (exact, highly optimized C)
        t0 = time.perf_counter()
        py = [divmod(a, b) for a, b in zip(us, vs)]
        t_py = time.perf_counter() - t0

        ok = True
        if validate:
            q, r = div(u, v)
            for (qq, rr), (qe, re_) in zip(
                    zip(bi.batch_to_ints(q), bi.batch_to_ints(r)), py):
                if (qq, rr) != (qe, re_):
                    ok = False
                    break
        rows.append({
            "bits": bits, "insts": insts, "impl": impl,
            "windowed": windowed,
            "mul_ms": round(t_mul * 1e3, 3),
            "div_ms": round(t_div * 1e3, 3),
            "div_over_mul": round(t_div / t_mul, 3),
            "py_int_ms": round(t_py * 1e3, 3),
            "exact": ok,
        })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="mul vs div throughput across precisions (Table 1)")
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[2 ** 10, 2 ** 12, 2 ** 14, 2 ** 16],
                    help="operand sizes in bits")
    ap.add_argument("--paper-range", action="store_true",
                    help="the paper's target sizes: 2^15..2^18 bits")
    ap.add_argument("--impl", default="blocked",
                    choices=list(K.IMPLS))
    ap.add_argument("--no-windowed", dest="windowed", action="store_false")
    ap.add_argument("--no-validate", dest="validate", action="store_false")
    ap.add_argument("--counts-only", action="store_true",
                    help="structural launch/op sweep (trace only; how "
                         "the 2^15..2^18 fused range is recorded)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append rows to a JSON file (keyed by "
                         "bits/impl/windowed, rewritten sorted)")
    args = ap.parse_args(argv)
    if args.paper_range:
        args.sizes = [2 ** 15, 2 ** 16, 2 ** 17, 2 ** 18]

    if args.counts_only:
        rows = run_counts(args.sizes, impl=args.impl,
                          windowed=args.windowed)
    else:
        rows = run(sizes=args.sizes, validate=args.validate,
                   impl=args.impl, windowed=args.windowed)
        print("bits,insts,impl,windowed,mul_ms,div_ms,div_over_mul,"
              "py_int_ms,exact")
        for r in rows:
            print(f"{r['bits']},{r['insts']},{r['impl']},{r['windowed']},"
                  f"{r['mul_ms']:.1f},{r['div_ms']:.1f},"
                  f"{r['div_over_mul']:.2f},{r['py_int_ms']:.1f},"
                  f"{r['exact']}")
        assert all(r["exact"] for r in rows)
    if args.json:
        _db()                                 # ensures sibling imports work
        from bigmul_sweep import merge_json   # the deterministic writer
        # merge_json keys on (bits, batch, impl); a "table1:" namespace
        # (plus a windowed tag) keeps these rows from colliding with
        # bigmul_sweep rows that share bits/batch/impl in the same file
        rows_keyed = [dict(r, batch=r["insts"],
                           impl="table1:" + r["impl"]
                           + ("" if r["windowed"] else "+unwindowed"))
                      for r in rows]
        merge_json(args.json, rows_keyed)
        print(f"wrote {args.json}")
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    main()
