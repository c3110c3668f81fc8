"""Additional coverage: SSD chunked oracle, jamba decode parity,
sharding-rule unit tests, serving service, windowed shinv property."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import configs
from repro.models import transformer as T


def test_ssd_chunked_matches_naive():
    from repro.models.mamba import _ssd_chunked, _ssd_naive
    key = jax.random.PRNGKey(0)
    b, t, h, hd, n = 2, 256, 4, 16, 8
    xh = jax.random.normal(key, (b, t, h, hd), jnp.float32) * 0.5
    dt_h = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(key, 1), (b, t, h)) - 1.0)
    a_h = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2),
                                     (h,)) * 0.3)
    bm = jax.random.normal(jax.random.fold_in(key, 3), (b, t, n)) * 0.5
    cm = jax.random.normal(jax.random.fold_in(key, 4), (b, t, n)) * 0.5
    y1 = _ssd_naive(xh, dt_h, a_h, bm, cm)
    y2 = _ssd_chunked(xh, dt_h, a_h, bm, cm, chunk=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)


def test_jamba_decode_matches_forward():
    """Hybrid (mamba + attn + MoE) decode parity with the parallel
    forward -- covers mamba conv-window and ssm-state decode paths.

    Capacity is raised into the drop-free regime: GShard capacity
    dropping is batch-dependent (prefill tokens compete for expert
    slots; a single decode token never overflows), so parity is only
    defined when nothing drops."""
    import dataclasses
    cfg = configs.get_config("jamba-1.5-large-398b").reduced()
    cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    key = jax.random.PRNGKey(7)
    params = T.init_params(cfg, key)
    b, s = 1, 8
    toks = jax.random.randint(key, (b, s), 1, cfg.vocab)
    x = T._embed_inputs(params, {"tokens": toks}, cfg)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s)).astype(jnp.int32)
    h, _ = T._backbone(params, x, cfg, pos, "train")
    full_logits = T._logits(params, h[:, -1:], cfg)[:, 0]
    cache = T.init_cache(cfg, b, s)
    for i in range(s):
        logits, cache = T.forward_decode(
            params, cache, {"token": toks[:, i]}, jnp.int32(i), cfg)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(full_logits, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_param_spec_rules():
    """Sharding rules: TP dims, FSDP placement, stacked-leaf offset."""
    import os
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import PartitionSpec as P
from repro.launch.specs import param_spec
mesh = jax.make_mesh((2, 4), ("data", "model"), devices=jax.devices()[:8])
# column-parallel mlp wi with FSDP: d_ff on model, d_model on data
s = param_spec("/blocks/slot0/mlp/wi", (12, 64, 128), None, mesh, True)
assert s == P(None, "data", "model"), s
# row-parallel wo
s = param_spec("/blocks/slot0/mlp/wo", (12, 128, 64), None, mesh, False)
assert s == P(None, "model", None), s
# embed: vocab on model
s = param_spec("/embed", (512, 64), None, mesh, False)
assert s == P("model", None), s
# experts stacked: expert dim on model
s = param_spec("/blocks/slot0/moe/experts/wi", (12, 8, 64, 128),
               None, mesh, False)
assert s == P(None, "model", None, None), s
# non-divisible stays replicated
s = param_spec("/blocks/slot0/attn/wk", (12, 64, 6), None, mesh, False)
assert s == P(None, None, None), s
print("SPEC_RULES_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "SPEC_RULES_OK" in r.stdout, (r.stdout, r.stderr[-1500:])


def test_bigint_service_exact_and_splitting():
    from repro.serving.bigint_service import BigintDivisionService
    rnd = random.Random(3)
    m = 16
    svc = BigintDivisionService(m_limbs=m, batch_buckets=(4,))
    us = [rnd.randint(0, 2 ** (16 * m) - 1) for _ in range(10)]
    vs = [rnd.randint(1, 2 ** (16 * m // 2) - 1) for _ in range(10)]
    q, r = svc.divide(us, vs)          # forces bucket splitting (10 > 4)
    for u, v, qq, rr in zip(us, vs, q, r):
        assert (qq, rr) == divmod(u, v)


def test_services_under_a_mesh_run_per_device_rows():
    """Under a mesh the bucket runs in a shard_map (Pallas kernels
    cannot be partitioned by jit shardings): results stay exact and
    each KernelPlan describes one device's share of the bucket."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.serving.bigint_service import BigintDivisionService
    from repro.serving.modexp_service import ModArithService
    mesh = Mesh(np.array(jax.devices()[:1]), ("chips",))
    rnd = random.Random(4)
    m = 8
    div = BigintDivisionService(m_limbs=m, mesh=mesh, batch_buckets=(4,))
    us = [rnd.randint(0, 2 ** (16 * m) - 1) for _ in range(3)]
    vs = [rnd.randint(1, 2 ** (16 * m // 2) - 1) for _ in range(3)]
    q, r = div.divide(us, vs)
    assert list(zip(q, r)) == [divmod(u, v) for u, v in zip(us, vs)]
    assert div.kernel_plans[4].grid_rows == 4 // mesh.size
    mod = ModArithService(m_limbs=4, mesh=mesh, batch_buckets=(2,))
    v = 1000003
    assert mod.modexp([7, 9], [12, 0], v) == [pow(7, 12, v), 1]


@given(st.integers(0, 2 ** 512 - 1), st.integers(1, 2 ** 256 - 1))
@settings(max_examples=25, deadline=None)
def test_windowed_divmod_property(u, v):
    from repro.core import bigint as bi
    from repro.core import shinv as S
    m = 32
    q, r = S.divmod_batch(jnp.asarray(bi.batch_from_ints([u], m)),
                          jnp.asarray(bi.batch_from_ints([v], m)),
                          windowed=True)
    assert (bi.batch_to_ints(q)[0], bi.batch_to_ints(r)[0]) == divmod(u, v)


def test_mrope_positions_text_only_equals_rope_t_section():
    """For text (t==h==w positions), M-RoPE with equal sections reduces
    to plain RoPE on the shared positions."""
    from repro.models import layers as L
    key = jax.random.PRNGKey(0)
    b, s, h, hd = 1, 16, 2, 32
    x = jax.random.normal(key, (b, s, h, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    pos3 = jnp.broadcast_to(pos, (3, b, s))
    r1 = L.apply_rope(x, pos)
    r2 = L.apply_mrope(x, pos3, sections=(6, 5, 5))
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                               rtol=1e-5, atol=1e-5)


def test_zero1_spec_no_duplicate_axes():
    import os
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import PartitionSpec as P
from repro.optim.adamw import zero1_spec
mesh = jax.make_mesh((2, 4), ("data", "model"), devices=jax.devices()[:8])
# FSDP already on data: unchanged
assert zero1_spec(P("data", "model"), (8, 8), mesh) == P("data", "model")
# plain TP param: data added on first divisible free dim
assert zero1_spec(P(None, "model"), (8, 8), mesh) == P("data", "model")
# nothing divisible: unchanged
assert zero1_spec(P(None,), (3,), mesh) == P(None,)
print("ZERO1_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "ZERO1_OK" in r.stdout, (r.stdout, r.stderr[-1500:])
