"""End-to-end batched division service benchmark (the serving driver
for the paper's workload: many independent same-precision divisions)."""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bigint as bi
from repro.serving.bigint_service import BigintDivisionService
from repro.utils import compile_cache


def run(m_limbs=256, batch=256):
    svc = BigintDivisionService(m_limbs=m_limbs)
    rng = np.random.default_rng(5)
    us = [bi._rand_big(rng, 0, bi.BASE ** (m_limbs - 2))
          for _ in range(batch)]
    vs = [bi._rand_big(rng, 1, bi.BASE ** (m_limbs // 2))
          for _ in range(batch)]
    svc.divide(us, vs)                       # warmup/compile
    t0 = time.perf_counter()
    q, r = svc.divide(us, vs)
    dt = time.perf_counter() - t0
    # spot-check exactness
    for i in (0, batch // 2, batch - 1):
        assert (q[i], r[i]) == divmod(us[i], vs[i])
    return {"us_per_batch": dt * 1e6, "divs_per_s": batch / dt}


if __name__ == "__main__":
    compile_cache.enable()
    print(run())
