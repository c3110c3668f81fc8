"""Thin wrappers over JAX APIs the repo calls from several places."""

from __future__ import annotations

import jax


def shard_map(fn, mesh, in_specs, out_specs, check_vma: bool = False):
    """`jax.shard_map` with the replication check off by default."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
