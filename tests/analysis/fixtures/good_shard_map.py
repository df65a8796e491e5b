"""Fixture: shard_map routed through the compat shim — clean.

Mentioning shard_map in a docstring or comment is fine: the rule is an
AST pass, not a grep. Everything executable goes through
``shard_map_compat`` (the repo's one shard_map call site).
"""

from tpu_gossip.dist._compat import shard_map_compat


def shimmed(f, mesh, specs):
    # shard_map spelled out here in a comment is not a finding
    return shard_map_compat(f, mesh=mesh, in_specs=specs, out_specs=specs)
