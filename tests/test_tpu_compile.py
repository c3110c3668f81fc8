"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: these
tests lower and compile the Pallas kernels at real widths for a
described v5e, so a kernel Mosaic would refuse (block shapes, operand
types, layouts, scoped VMEM) fails here and not on the chip.  Nothing
runs; exactness stays with the interpret-mode tests.
"""

from __future__ import annotations

import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import modarith as MA
from repro.core import shinv as S
from repro.kernels import bigmul
from repro.kernels import ops as K


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the kernels compiled
    (not interpreted) and no persistent cache: a compile for a
    described chip cannot be read back from it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:        # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        mp.setattr(K, "interpret_mode", lambda: False)
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        jax.clear_caches()            # no trace made in interpret mode
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.clear_caches()
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _limbs(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_mul_pallas_batched_2e15(one_chip):
    x = _limbs(one_chip, 16, 2048)
    _assert_kernel(lambda u, v: bigmul.mul_pallas_batched(u, v, 4096), x, x)


@pytest.mark.parametrize("bits,path", [(2 ** 12, "unrolled"),
                                       (2 ** 15, "grid")])
def test_fused_divmod(one_chip, bits, path):
    m = bits // 16
    w = m + S.PAD
    assert K.fused_path(2 * w, w, w, -(-2 * w // 128) * 128) == path
    x = _limbs(one_chip, 16, m)
    _assert_kernel(lambda u, v: S.divmod_batch(u, v, impl="pallas_fused"),
                   x, x)


def test_barrett_reduce_2048(one_chip):
    m = 128
    ctx = MA.BarrettContext(
        v=_limbs(one_chip, m), mu=_limbs(one_chip, MA.barrett_width(m)),
        k=jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    _assert_kernel(
        lambda c, x: MA.reduce_shared(c, x, impl="pallas_fused"),
        ctx, _limbs(one_chip, 16, 2 * m))
