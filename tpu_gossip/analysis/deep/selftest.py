"""Adversarial self-test fixtures for the two wire-safety deep passes.

A static gate that silently stops firing is worse than no gate: CI would
keep passing while the rail it trusts has rotted. This module builds two
DELIBERATELY broken synthetic entries — never part of the real matrix —
and asserts the passes still report them:

- :func:`divergent_collective_entry` — a ``shard_map`` body that issues
  a ``psum`` in ONE arm of a ``lax.cond`` gated on a shard-varying
  predicate (the local shard's own data). Bit-for-bit the deadlock shape
  ``deep-collective-uniformity`` exists for; jax traces it without
  complaint, which is the point.
- :func:`divergent_dcn_collective_entry` — the same deadlock shape on
  the 2-D ``(hosts, peers)`` cluster mesh, with the conditional
  collective over the slow ``"hosts"`` (DCN) axis. The two-level
  transport gates its DCN stage on psum'd replicated headers; this
  fixture is the rotted variant (raw shard-varying predicate) and keeps
  the rail honest on the axis where a hang is the most expensive.
- :func:`unpack_spike_entry` — a packed entry whose trace hand-rolls the
  LSB-first shift-and-mask decode OUTSIDE ``core/packed.py``,
  materializing a full-width (N, M) bool plane the budget never priced.
  ``deep-transient-liveness`` must name this file's decode line.
- :func:`word_kernel_entry` — the GOOD twin: the packed-native round
  shape (word-level bitwise/popcount ops through ``kernels/packed_ops``,
  decode only via the codec). ``deep-transient-liveness`` must stay
  SILENT on it — a rail that flags the sanctioned kernels would push
  every packed-native op behind pragmas and rot the gate the other way.

:func:`run_selftest` runs all four and returns the failures (empty =
the rails fire where they must and only there). CI runs it as a step of
the lint-deep job (``python -m tpu_gossip.analysis --deep-selftest``);
the same fixtures back tests/analysis/test_collectives.py /
test_liveness.py.
"""

from __future__ import annotations

__all__ = [
    "divergent_collective_entry",
    "divergent_dcn_collective_entry",
    "unpack_spike_entry",
    "word_kernel_entry",
    "run_selftest",
]

_N_FIXTURE = 32  # tiny synthetic swarm rows (fast to trace, full-width)
_M_FIXTURE = 16  # slot width: packs to 2 uint8 words per row


def _entry(name: str, fn, state, *, packed: bool = False):
    """A synthetic TracedEntry outside the real matrix (selftest only)."""
    import jax

    from tpu_gossip.analysis.entrypoints import EntryPoint, TracedEntry

    ep = EntryPoint(
        name=name, engine="selftest", kind="round",
        audit_check="selftest", build=lambda: (fn, state),
        n_peers=_N_FIXTURE, packed=packed,
    )
    te = TracedEntry(ep=ep, state=state)
    te.jaxpr, te.out_shape = jax.make_jaxpr(fn, return_shape=True)(state)
    return name, te


def divergent_collective_entry():
    """(name, TracedEntry): a collective under a shard-varying branch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_gossip.dist._compat import shard_map_compat
    from tpu_gossip.dist.mesh import AXIS, make_mesh

    mesh = make_mesh()

    def body(x):
        # the predicate reads the SHARD'S OWN slice: shard-varying, so
        # the arms below rendezvous on some shards and not others
        pred = x[0] > 0.0
        return jax.lax.cond(
            pred,
            # the psum's replicated result re-typed as varying, so the
            # arms trace under check_vma; the collective stays in one arm
            lambda v: jax.lax.pcast(
                jax.lax.psum(v, AXIS), AXIS, to="varying"
            ),
            lambda v: v,
            x,
        )

    fn = shard_map_compat(
        body, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)
    )
    state = jnp.arange(float(mesh.size * 4)).reshape(mesh.size * 4)
    return _entry("selftest[divergent-collective]", fn, state)


def divergent_dcn_collective_entry():
    """(name, TracedEntry): a DCN-axis collective under a shard-varying
    branch on the 2-D cluster mesh — the multi-host deadlock variant."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_gossip.cluster.topology import (
        DEVICE_AXIS,
        HOST_AXIS,
        make_cluster_mesh,
    )
    from tpu_gossip.dist._compat import shard_map_compat

    mesh = make_cluster_mesh(hosts=2)
    axes = (HOST_AXIS, DEVICE_AXIS)

    def body(x):
        # shard-varying predicate (the shard's own slice) guarding a
        # collective over the slow cross-host axis: some host rows
        # rendezvous on the DCN psum, the others never post it
        pred = x[0] > 0.0
        return jax.lax.cond(
            pred,
            # the psum's replicated result re-typed as varying, so the
            # arms trace under check_vma; the collective stays in one arm
            lambda v: jax.lax.pcast(
                jax.lax.psum(v, HOST_AXIS), HOST_AXIS, to="varying"
            ),
            lambda v: v,
            x,
        )

    fn = shard_map_compat(
        body, mesh=mesh, in_specs=P(axes), out_specs=P(axes)
    )
    state = jnp.arange(float(mesh.size * 4)).reshape(mesh.size * 4)
    return _entry("selftest[divergent-dcn-collective]", fn, state)


def unpack_spike_entry():
    """(name, TracedEntry): a hand-rolled decode outside the codec."""
    import jax.numpy as jnp

    from tpu_gossip.core.packed import pack_bits

    words = pack_bits(
        (jnp.arange(_N_FIXTURE * _M_FIXTURE) % 3 == 0).reshape(
            _N_FIXTURE, _M_FIXTURE
        )
    )

    def rogue(state):
        w = state["seen"]
        # the forbidden shape: shift-and-mask decode of packed words in
        # THIS file, not core/packed.py — a second (N, M) bool plane
        bits = (w[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & jnp.uint8(1)
        plane = bits.reshape(w.shape[0], -1)[:, :_M_FIXTURE] != 0
        return plane.sum()

    return _entry(
        "selftest[unpack-spike]", rogue, {"seen": words}, packed=True
    )


def word_kernel_entry():
    """(name, TracedEntry): the sanctioned packed-native kernel shape."""
    import jax.numpy as jnp

    from tpu_gossip.core.packed import bit_column, pack_bits
    from tpu_gossip.kernels import packed_ops as po

    words = pack_bits(
        (jnp.arange(_N_FIXTURE * _M_FIXTURE) % 3 == 0).reshape(
            _N_FIXTURE, _M_FIXTURE
        )
    )

    def good(state):
        w = state["seen"]
        # one round's worth of word algebra: merge, stale-filter,
        # forward-once latch, popcount billing — all at word width in
        # the kernel tier, plus a codec bit_column read
        merged = po.or_words(w, po.andnot_words(w, w))
        latched = po.and_words(merged, po.not_words(w, _M_FIXTURE))
        return (
            jnp.sum(po.popcount_rows(latched))
            + jnp.sum(po.rows_any(merged))
            + jnp.sum(bit_column(w, 0))
        )

    return _entry(
        "selftest[word-kernel]", good, {"seen": words}, packed=True
    )


def run_selftest() -> list[str]:
    """Run the adversarial fixtures; returns failure descriptions
    (empty = the rails fire where they must and only there)."""
    from tpu_gossip.analysis.deep.collectives import RULE as COLL_RULE
    from tpu_gossip.analysis.deep.collectives import entry_program
    from tpu_gossip.analysis.deep.liveness import RULE as LIVE_RULE
    from tpu_gossip.analysis.deep.liveness import codec_findings

    failures: list[str] = []

    name, te = divergent_collective_entry()
    ops, findings = entry_program(name, te)
    if not ops:
        failures.append(
            f"{name}: extracted an EMPTY collective program (the psum "
            "under the cond arm was not seen)"
        )
    if not any(f.rule == COLL_RULE and "diverges" in f.message
               for f in findings):
        failures.append(
            f"{name}: {COLL_RULE} did not fire on a collective under a "
            "shard-varying branch arm"
        )

    name, te = divergent_dcn_collective_entry()
    ops, findings = entry_program(name, te)
    from tpu_gossip.dist.mesh import axis_kind
    if not any(
        axis_kind(ax) == "dcn" for op in ops for ax in op.axes
    ):
        failures.append(
            f"{name}: the conditional host-axis psum was not recorded "
            "as a dcn-class collective"
        )
    if not any(f.rule == COLL_RULE and "diverges" in f.message
               for f in findings):
        failures.append(
            f"{name}: {COLL_RULE} did not fire on a DCN-axis collective "
            "under a shard-varying branch arm"
        )

    name, te = unpack_spike_entry()
    findings = codec_findings(name, te)
    if not any(
        f.rule == LIVE_RULE and f.file.endswith("selftest.py")
        for f in findings
    ):
        failures.append(
            f"{name}: {LIVE_RULE} did not fire on an out-of-codec decode"
        )

    name, te = word_kernel_entry()
    findings = codec_findings(name, te)
    if findings:
        failures.append(
            f"{name}: {LIVE_RULE} fired on sanctioned word-level kernel "
            f"ops ({findings[0].file}:{findings[0].line} "
            f"{findings[0].message[:60]}…)"
        )
    return failures
