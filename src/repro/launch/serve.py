"""Serving launcher CLI: two services.

  LM decode demo (reduced config, greedy sampling):
    python -m repro.launch.serve --arch smollm-135m --tokens 32

  Batched big-integer division service (the paper's workload):
    python -m repro.launch.serve --bigint --limbs 256 --batch 64
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.models import transformer as T
from repro.utils import compile_cache


def serve_lm(args):
    cfg = configs.get_config(args.arch).reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cache = T.init_cache(cfg, args.batch, args.tokens + 8)
    step = jax.jit(lambda p, c, b, i: T.forward_decode(p, c, b, i, cfg))
    tok = jnp.zeros((args.batch,), jnp.int32)
    out = []
    t0 = time.perf_counter()
    for i in range(args.tokens):
        logits, cache = step(params, cache, {"token": tok}, jnp.int32(i))
        tok = jnp.argmax(logits[:, : cfg.vocab], -1).astype(jnp.int32)
        out.append(np.asarray(tok))
    dt = time.perf_counter() - t0
    print(f"decoded {args.tokens} tokens x batch {args.batch} in "
          f"{dt*1e3:.0f} ms ({args.tokens*args.batch/dt:.0f} tok/s)")
    print("sample:", [int(x[0]) for x in out[:16]])


def serve_bigint(args):
    from repro.serving.bigint_service import BigintDivisionService
    from repro.core import bigint as bi
    svc = BigintDivisionService(m_limbs=args.limbs)
    rng = np.random.default_rng(0)
    us = [bi._rand_big(rng, 0, bi.BASE ** (args.limbs - 2))
          for _ in range(args.batch)]
    vs = [bi._rand_big(rng, 1, bi.BASE ** (args.limbs // 2))
          for _ in range(args.batch)]
    svc.divide(us[:4], vs[:4])            # warm
    t0 = time.perf_counter()
    q, r = svc.divide(us, vs)
    dt = time.perf_counter() - t0
    assert all(u == qq * vv + rr and rr < vv
               for u, vv, qq, rr in zip(us, vs, q, r))
    print(f"divided {args.batch} x {args.limbs*16}-bit ints in "
          f"{dt*1e3:.0f} ms ({args.batch/dt:.0f} div/s), all exact")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=configs.list_archs())
    ap.add_argument("--bigint", action="store_true")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--limbs", type=int, default=256)
    args = ap.parse_args()
    if args.bigint:
        serve_bigint(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    compile_cache.enable()
    main()
