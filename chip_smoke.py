"""Smoke test of the served division and modexp paths on a TPU.

Drives the system through the entry points a user calls -- the async
serving frontend (`AsyncFrontend`) over `BigintDivisionService` and
`ModArithService` -- on the default `pallas_fused` kernels, and fails
unless every answer equals Python's and every bucket ran compiled
Pallas kernels with nothing degraded:

  div 2^15   2048-limb operands, bucket 128   the paper's Table 1 sizes
  div 2^18   16384-limb operands, bucket 16   (2^22 bits per batch)
  modexp     2048-bit modulus and exponents   OpenSSL `speed rsa2048`

Each phase submits a few concurrent requests that coalesce into one
bucket (edge rows included), checks every result against `divmod` /
`pow`, and fails when a bucket's KernelPlan is not `pallas_fused`, a
bucket degraded, the frontend counted a degradation, or the bucket's
compiled program holds no `tpu_custom_call`.

    python chip_smoke.py            one chip, all three phases
    python chip_smoke.py --chips 4  the 2^15-bit bucket on a 4-chip mesh
                                    against the same batch on one chip

The times printed are smoke timings (the first round includes its
compilation), not benchmark numbers.  The last line of standard output
is one JSON object naming the device; it is printed only when every
check passed.  Without a TPU the script exits non-zero.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 20260
DIV_PHASES = ((2048, 128), (16384, 16))     # (m_limbs, bucket)
MODEXP_LIMBS, MODEXP_BUCKET = 128, 8


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"smoke check failed: {msg}")


def rand_int(rng: random.Random, bits: int) -> int:
    return rng.getrandbits(bits) if bits > 0 else 0


def division_requests(m: int, rows: int, rng: random.Random):
    """Request columns (us, vs) for `rows` rows split into four
    requests; the edge rows are u < v, v = 1 and all-0xFFFF limbs."""
    bits = 16 * m
    top = (1 << bits) - 1
    us, vs = [], []
    for i in range(rows):
        us.append(rand_int(rng, bits - rng.randrange(0, 64)))
        vs.append(rand_int(rng, rng.randrange(2, bits)) | 1)
    edges = [(5, top), (rand_int(rng, bits), 1), (top, top),
             (top, rand_int(rng, bits // 2) | 1), (top, 1)]
    for k, (u, v) in enumerate(edges):
        us[k * (rows // len(edges))], vs[k * (rows // len(edges))] = u, v
    cut = [0, rows // 3, rows // 2, rows - rows // 8, rows]
    return [(us[a:b], vs[a:b]) for a, b in zip(cut, cut[1:])]


def modexp_requests(rng: random.Random):
    """(v, [(as, es)]) for MODEXP_BUCKET rows in three requests that
    share one 2048-bit odd modulus; edges e = 0, e = 1, a = 0, a = v-1."""
    bits = 16 * MODEXP_LIMBS
    v = rand_int(rng, bits) | (1 << (bits - 1)) | 1
    a = [rng.randrange(v) for _ in range(MODEXP_BUCKET)]
    e = [rand_int(rng, bits) for _ in range(MODEXP_BUCKET)]
    e[0], e[1], a[2], a[3] = 0, 1, 0, v - 1
    return v, [(a[:4], e[:4]), (a[4:6], e[4:6]), (a[6:], e[6:])]


async def serve(svc, op: str, requests, v=None):
    """One round: submit every request concurrently through a fresh
    frontend; returns (results, seconds, frontend metrics)."""
    from repro.serving.frontend import AsyncFrontend
    from repro.serving.policy import ServingPolicy
    fe = AsyncFrontend(svc, policy=ServingPolicy(coalesce_window=0.05))
    async with fe:
        t0 = time.perf_counter()
        out = await asyncio.gather(*(fe.submit(op, *cols, v=v)
                                     for cols in requests))
        dt = time.perf_counter() - t0
    return out, dt, fe


def frontend_checks(fe, n_requests: int) -> None:
    m = fe.metrics
    total = fe._counter_total
    check(total(m.degraded) == 0, "frontend degraded_total > 0")
    check(total(m.faults) == 0, "frontend counted chunk faults")
    cycles = sum(s.count for s in m.coalesced.series())
    check(cycles < n_requests, "requests did not coalesce")
    check(fe.dropped_requests() == 0, "frontend dropped requests")


def compile_bucket(name: str, lower):
    """Compile a bucket's program ahead of serving it (the served call
    reuses it) and fail unless it holds compiled Pallas kernels;
    returns (seconds, compiled)."""
    t0 = time.perf_counter()
    compiled = lower().compile()
    dt = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(),
          f"{name}: the compiled program holds no Pallas kernel")
    return dt, compiled


def plan_checks(svc, name: str) -> None:
    """Every compiled bucket planned pallas_fused and none degraded."""
    check(bool(svc.kernel_plans), f"{name}: no bucket compiled")
    for bucket, plan in svc.kernel_plans.items():
        check(plan.impl == "pallas_fused",
              f"{name}: bucket {bucket} ran {plan.impl}")
        check(plan.degraded_from == "",
              f"{name}: bucket {bucket} degraded from {plan.degraded_from}")


def div_name(m: int, bucket: int) -> str:
    return f"div 2^{(16 * m).bit_length() - 1} bucket {bucket}"


def report(name: str, compile_s: float, times, svc) -> None:
    plans = {b: p._asdict() for b, p in svc.kernel_plans.items()}
    print(f"[smoke timing, not a benchmark] {name}: compile "
          f"{compile_s:.3f} s (trace, lower, compile), first round "
          f"{times[0]:.3f} s, warm round {times[1]:.3f} s")
    print(f"[smoke] {name} kernel plans: {plans}")
    sys.stdout.flush()


def division_phase(m: int, bucket: int, rng: random.Random, mesh=None):
    """Compile one Table-1 bucket, serve it twice (first, warm) and
    check it; returns (service, warm (qs, rs), requests, warm seconds,
    compiled program)."""
    import jax
    import jax.numpy as jnp
    from repro.serving.bigint_service import BigintDivisionService
    name = div_name(m, bucket) + (
        f" on {mesh.size} chips" if mesh is not None else "")
    svc = BigintDivisionService(m, mesh=mesh, batch_buckets=(bucket,))
    reqs = division_requests(m, bucket, rng)
    spec = jax.ShapeDtypeStruct((bucket, m), jnp.uint32)
    compile_s, compiled = compile_bucket(
        name, lambda: svc._fn(bucket).lower(spec, spec))
    times = []
    for _ in range(2):
        out, dt, fe = asyncio.run(serve(svc, "divmod", reqs))
        times.append(dt)
        frontend_checks(fe, len(reqs))
        for (us, vs), (qs, rs) in zip(reqs, out):
            for u, v, q, r in zip(us, vs, qs, rs):
                check((q, r) == divmod(u, v), f"{name}: wrong divmod")
    plan_checks(svc, name)
    report(name, compile_s, times, svc)
    flat_q = [q for qs, _ in out for q in qs]
    flat_r = [r for _, rs in out for r in rs]
    return svc, (flat_q, flat_r), reqs, times[1], compiled


def modexp_phase(rng: random.Random) -> None:
    import jax
    import jax.numpy as jnp
    from repro.serving.modexp_service import ModArithService
    name = f"modexp {16 * MODEXP_LIMBS}-bit bucket {MODEXP_BUCKET}"
    svc = ModArithService(MODEXP_LIMBS, batch_buckets=(MODEXP_BUCKET,))
    v, reqs = modexp_requests(rng)
    t0 = time.perf_counter()
    jax.block_until_ready(svc.context(v))
    pre_s = time.perf_counter() - t0
    spec = jax.ShapeDtypeStruct((MODEXP_BUCKET, MODEXP_LIMBS), jnp.uint32)
    slots = jax.ShapeDtypeStruct((MODEXP_BUCKET,), jnp.int32)
    compile_s, _ = compile_bucket(
        name, lambda: svc._fn("modexp", MODEXP_BUCKET).lower(
            svc._slab, slots, spec, spec))
    times = []
    for _ in range(2):
        out, dt, fe = asyncio.run(serve(svc, "modexp", reqs, v=v))
        times.append(dt)
        frontend_checks(fe, len(reqs))
        for (as_, es), rs in zip(reqs, out):
            for a, e, r in zip(as_, es, rs):
                check(r == pow(a, e, v), f"{name}: wrong modexp")
    plan_checks(svc, name)
    print(f"[smoke timing, not a benchmark] {name}: Barrett precompute "
          f"{pre_s:.3f} s (its compile included)")
    report(name, compile_s, times, svc)


def four_chip_phase(rng: random.Random) -> None:
    """The 2^15-bit bucket on a 4-chip mesh against one chip: equal
    answers, and each chip holds and computes a quarter of the rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import bigint as bi
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    m, bucket = DIV_PHASES[0]
    state = rng.getstate()
    one, res1, reqs, warm1, c1 = division_phase(m, bucket, rng)
    rng.setstate(state)                      # the same batch again
    mesh = Mesh(np.array(devs[:4]), ("chips",))
    four, res4, reqs4, warm4, c4 = division_phase(m, bucket, rng, mesh=mesh)
    check(reqs4 == reqs, "the two runs saw different batches")
    check(res4 == res1, "4-chip results differ from the one-chip run")
    # each chip's shard of the output is its quarter of the rows
    us = [u for us_, _ in reqs for u in us_]
    vs = [v for _, vs_ in reqs for v in vs_]
    ua = jnp.asarray(bi.batch_from_ints(us, m))
    va = jnp.asarray(bi.batch_from_ints(vs, m))
    q, _ = four._fn(bucket)(ua, va)
    shards = sorted(q.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    check(len({s.device for s in shards}) == 4, "output not on 4 chips")
    quarter = bucket // 4
    for k, s in enumerate(shards):
        check(s.data.shape == (quarter, m), f"shard {k} is {s.data.shape}")
        rows = bi.batch_to_ints(np.asarray(s.data))
        check(rows == res1[0][k * quarter:(k + 1) * quarter],
              f"chip {k}'s rows differ from the one-chip run")
    # the per-chip program takes a quarter of the batch and no collective
    a4 = c4.memory_analysis().argument_size_in_bytes
    a1 = c1.memory_analysis().argument_size_in_bytes
    check(4 * a4 == a1, f"per-chip inputs {a4} B vs one chip {a1} B")
    text = c4.as_text()
    for coll in ("all-gather", "all-reduce", "all-to-all",
                 "collective-permute", "reduce-scatter"):
        check(coll not in text, f"4-chip program holds {coll}")
    print(f"[smoke timing, not a benchmark] {div_name(m, bucket)}: "
          f"warm round {warm1:.3f} s on one chip, {warm4:.3f} s on 4 chips;"
          f" per-chip input {a4} B of {a1} B; every chip computed its "
          f"{quarter} rows")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip mesh phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    from repro.utils import compile_cache
    print(f"[smoke] devices: {len(jax.devices())} x {dev.device_kind}; "
          f"compile cache {compile_cache.enable()}")

    rng = random.Random(SEED)
    if args.chips == 4:
        four_chip_phase(rng)
    else:
        for m, bucket in DIV_PHASES:
            division_phase(m, bucket, rng)
        modexp_phase(rng)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
