"""Fixture: a collective hidden behind a LAMBDA-WRAPPED branch arm.

This module is deliberately blind-spot-shaped for the AST tier: it
routes shard_map through the sanctioned compat shim (so the 65-line
``raw-shard-map`` rule in rules_shardmap.py has nothing to say) and
tucks a ``psum`` inside one lambda arm of a ``lax.cond`` gated on the
shard's OWN data — the classic multi-host deadlock. No AST rule can
prove which arm a traced cond takes or that the arms' collective
sequences differ; only the deep pass over the traced jaxpr
(``deep-collective-uniformity``) can. tests/analysis/test_collectives.py
asserts exactly that split: the AST lint of THIS FILE is clean, the
trace of ``build(mesh)`` is a finding.
"""

import jax
from jax.sharding import PartitionSpec as P

from tpu_gossip.dist._compat import shard_map_compat
from tpu_gossip.dist.mesh import AXIS


def build(mesh):
    """A shard_mapped round whose reduce rendezvous depends on local data."""

    def body(x):
        # shard-varying predicate: each shard reads its own slice
        return jax.lax.cond(
            x[0] > 0.0,
            # arm 1 rendezvouses (re-typed varying so the arms trace
            # under check_vma)...
            lambda v: jax.lax.pcast(jax.lax.psum(v, AXIS), AXIS, to="varying"),
            lambda v: v,  # ...arm 0 never does
            x,
        )

    return shard_map_compat(
        body, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)
    )
