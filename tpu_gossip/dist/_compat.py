"""The repo's one ``shard_map`` call site (the ``raw-shard-map`` lint rule
routes every engine through it)."""

from __future__ import annotations

import jax

__all__ = ["shard_map_compat"]


def shard_map_compat(f, *, mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map`` with the repo's keyword set."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
