"""Barrett amortization benchmark: cached shinv vs per-step division.

The cost model (paper Sec 2.3 + EXPERIMENTS.md "modexp amortization"):
one division costs 5-7 full multiplications, dominated by the Newton
refinement that computes shinv_h(v).  A Barrett reduction against a
*cached* shinv costs ~2 truncated multiplications.  A modexp ladder
performs ~2 modular reductions per exponent bit against ONE modulus, so
the refinement amortizes away and the predicted per-reduction speedup
approaches (5..7)/2.

Both modexp paths run the IDENTICAL host-driven square-and-multiply
ladder over compiled batched primitives; the only difference is the
reduction executable: `barrett` reduces against the cached context,
`divmod` re-derives the shifted inverse every step (what serving
without the modarith subsystem would do).

Measured per precision:

  red/s        batched Barrett reductions per second (cached ctx)
  div_red/s    batched divmod-based reductions per second
  speedup      per-reduction ratio t_div / t_barrett
  crossover    N* = t_ctx / (t_div - t_barrett): reductions needed
               before precomputing the context pays for itself
  modexp_x     end-to-end ladder wall-time ratio divmod / Barrett

Each row also records the STRUCTURAL launch telemetry of the two
reduction executables straight off their traced programs
(`red_launches` / `div_launches`, repro.utils.jaxpr_stats) next to the
cost model's predictions (`model_red_launches` /
`model_div_launches`, repro.obs.costmodel) -- the launch-count side of
the (5..7)/2 amortization claim.  Rows merge deterministically into
BENCH_modexp.json keyed by (bits, batch, impl) through the shared
writer (repro.obs.report.merge_json).

Run:  PYTHONPATH=src python benchmarks/modexp.py [--bits 256,512,1024]
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bigint as bi
from repro.core import modarith as MA
from repro.core import shinv as S
from repro.kernels import ops as K
from repro.obs import costmodel as CM
from repro.obs import report as RPT
from repro.utils import jaxpr_stats as JS
from repro.utils import compile_cache

_SCHEMA = 1


def _bench(fn, *args, reps=3):
    fn(*args)                   # compile + warmup
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def run(sizes=(256, 512, 1024), batch=16, exp_bits=32, impl="blocked",
        validate=True, out_path=None):
    rng = np.random.default_rng(0)
    rows = []
    print(f"batch={batch} exp_bits={exp_bits} impl={impl}")
    print(f"{'bits':>6} {'red/s':>10} {'div_red/s':>10} {'speedup':>8} "
          f"{'crossover':>10} {'modexp_x':>9}")
    for bits in sizes:
        m = bi.width_for_bits(bits)
        v_int = bi._rand_big(rng, bi.BASE ** (m - 1), bi.BASE ** m) | 1
        a_int = [bi._rand_big(rng, 0, v_int) for _ in range(batch)]
        # force a set MSB so every instance walks the same ladder length
        e_int = [bi._rand_big(rng, 0, 1 << exp_bits)
                 | (1 << (exp_bits - 1)) for _ in range(batch)]
        x_int = [bi._rand_big(rng, 0, bi.BASE ** (2 * m))
                 for _ in range(batch)]
        v1 = jnp.asarray(bi.from_int(v_int, m))
        v2 = jnp.asarray(bi.batch_from_ints([v_int] * batch, 2 * m))
        x = jnp.asarray(bi.batch_from_ints(x_int, 2 * m))

        # --- the amortized constant: one shinv per modulus
        pre = jax.jit(lambda vv: MA.barrett_precompute(vv, impl=impl))
        t_ctx = _bench(pre, v1)
        ctx = jax.block_until_ready(pre(v1))

        # --- compiled primitives (reduction is the ONLY difference)
        bar_red = jax.jit(lambda xx: MA.reduce_shared(ctx, xx, impl=impl))
        div_red = jax.jit(jax.vmap(
            lambda xi, vi: S.divmod_fixed(xi, vi, impl=impl)[1][:m]))
        mul2 = jax.jit(jax.vmap(
            lambda ui, wi: K.mul(ui, wi, 2 * m, impl=impl)))
        sel = jax.jit(lambda cand, keep, bits_: jnp.where(
            (bits_ != 0)[:, None], cand, keep))

        # structural launch telemetry of the two reduction executables
        # vs the cost model (the launch side of the Barrett claim: one
        # fused launch -- or 2 truncated-mul launches -- against the
        # cached shinv, a full 2*iters+1 divmod without it)
        red_launches, _ = JS.trace_counts(bar_red, x)
        div_launches, _ = JS.trace_counts(div_red, x, v2)
        model_red = CM.barrett_launches(impl)
        model_div = CM.divmod_launches(2 * m, impl)

        t_bar = _bench(bar_red, x) / batch
        t_div = _bench(div_red, x, v2) / batch

        # --- identical host-driven ladders, swapped reduction
        bit_cols = [jnp.asarray(
            np.array([(ei >> j) & 1 for ei in e_int], np.uint32))
            for j in range(exp_bits - 2, -1, -1)]      # MSB consumed below
        a_r = bar_red(jnp.asarray(bi.batch_from_ints(a_int, 2 * m)))

        def ladder(red, *red_extra):
            def go(_):
                r = a_r                                # MSB is always 1
                for bits_ in bit_cols:
                    r = red(mul2(r, r), *red_extra)
                    cand = red(mul2(r, a_r), *red_extra)
                    r = sel(cand, r, bits_)
                return r
            return go

        f_bar = ladder(bar_red)
        f_div = ladder(div_red, v2)
        t_mb = _bench(f_bar, None)
        t_md = _bench(f_div, None)

        if validate:
            ref = [pow(ai, ei, v_int) for ai, ei in zip(a_int, e_int)]
            assert bi.batch_to_ints(np.asarray(f_bar(None))) == ref, \
                "barrett ladder mismatch"
            assert bi.batch_to_ints(np.asarray(f_div(None))) == ref, \
                "divmod ladder mismatch"
            assert bi.batch_to_ints(np.asarray(bar_red(x))) == \
                [xi % v_int for xi in x_int], "reduce mismatch"

        cross = t_ctx / max(t_div - t_bar, 1e-12)
        rows.append(dict(
            bits=bits, batch=batch, impl=impl,
            red_s=round(1 / t_bar, 2), div_s=round(1 / t_div, 2),
            speedup=round(t_div / t_bar, 3),
            crossover=round(cross, 1),
            modexp_x=round(t_md / t_mb, 3), t_ctx=round(t_ctx, 4),
            red_launches=red_launches,
            model_red_launches=model_red,
            div_launches=div_launches,
            model_div_launches=model_div,
            launch_match=(red_launches == model_red
                          and div_launches == model_div),
            backend=jax.default_backend(), schema=_SCHEMA))
        print(f"{bits:>6} {1 / t_bar:>10.1f} {1 / t_div:>10.1f} "
              f"{t_div / t_bar:>8.2f} {cross:>10.1f} {t_md / t_mb:>9.2f}"
              f"   red_launches={red_launches} (model {model_red})")
        if out_path:            # survive partial/killed runs
            RPT.merge_json(out_path, rows)
    if not all(r["launch_match"] for r in rows):
        raise SystemExit("launch count vs cost model FAILED")
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--bits", default="256,512,1024")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--exp-bits", type=int, default=32)
    ap.add_argument("--impl", default="blocked")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..",
        "BENCH_modexp.json"))
    ap.add_argument("--no-out", action="store_true",
                    help="don't write BENCH_modexp.json")
    ap.add_argument("--no-validate", action="store_true")
    args = ap.parse_args()
    run(sizes=tuple(int(s) for s in args.bits.split(",")),
        batch=args.batch, exp_bits=args.exp_bits, impl=args.impl,
        validate=not args.no_validate,
        out_path=None if args.no_out else os.path.normpath(args.out))
