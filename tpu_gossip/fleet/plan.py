"""Campaign plans: parse a campaign TOML, compile a batched swarm fleet.

A *campaign* turns the chaos catalogue from ~6 hand-written trajectories
into a **Monte Carlo certification run**: K independent swarms — the
*lanes* — drawn from a sampled distribution over fault space, compiled
into ONE batched program the fleet engine (fleet/engine.py) vmaps over.
Reliability then comes back as *quantiles with confidence intervals per
scenario family* (the delivery-ratio frame of *Reliable Probabilistic
Gossip over Large-Scale Random Topologies*, PAPERS.md) instead of one
sample per TOML, and controller-bound sweeps locate where the declared
contract breaks (the AIMD-bound question PeerSwap-style adaptive refresh
raises, PAPERS.md).

A campaign TOML holds one ``[campaign]`` table, one ``[base]`` run config
(every knob the lanes share), and ``[[family]]`` entries — each naming a
scenario file from the catalogue, a seed count, and optional
``[[family.sweep]]`` axes::

    [campaign]
    name = "lossy-sweep"
    seed = 0

    [base]
    peers  = 96
    rounds = 30
    slots  = 4
    fanout = 2
    mode   = "push"

    [[family]]
    name     = "lossy"
    scenario = "scenarios/lossy_links.toml"
    seeds    = 32

    [[family.sweep]]
    axis = "phase.loss"
    dist = "uniform"
    lo   = 0.05
    hi   = 0.6

**The shared-static-shape rule.** One compile serves all K lanes, so
every lane must share every jit-static property: same n, m, horizon,
``max_inject``, fanout-table width. The sampled axes are exactly the
ones that ride TRACED leaves — fault-phase parameters (per-phase table
values), traffic rates (a traced scalar; ``max_inject`` is pinned to the
largest sampled rate, so one compile serves every rate), and
controller bounds (per-lane CLAMPED fanout tables over one global-width
spec). An axis that would move a static shape — peers, slots, rounds,
TTL, Bloom width — is rejected at parse time (exit 2 from the CLI),
and after compilation every lane's plan pytree is structure-checked
against lane 0 as a backstop: a mismatch can never reach ``vmap``.

**Scenario-family unification.** Families compile their scenarios
independently, then unify to one static structure: per-phase tables are
zero-padded to the widest phase count (padded rows are quiescent and
unreachable — ``phase_of_round`` never names them), the ``has_*`` flags
become the OR across lanes, and lanes whose schedule lacks a fault class
run its machinery over all-zero tables — VALUE-identical to not running
it (the quiescent-scenario contract, tests/sim/test_faults.py), so a
mixed catalogue batches into one program without changing any lane's
trajectory.

**Determinism.** Lane k's root key is
``fold_in(fold_in(key(campaign_seed), FLEET_STREAM_SALT), k)`` — the
registered fleet stream (core/streams.py), derived host-side at compile
time. The conformance contract: lane k of the batched run is
BIT-IDENTICAL (full state + integer stats) to a solo ``simulate`` of
``campaign.lane(k)`` — test-pinned at composed scenario×stream×control
cells (tests/sim/test_fleet.py), and cross-checked across processes by
the fleet-smoke CI job's serial digest comparison.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from tpu_gossip.core.streams import FLEET_STREAM_SALT  # noqa: F401 (re-export)
from tpu_gossip.faults.scenario import (
    ScenarioError,
    _parse_value,
    _strip_comment,
)

__all__ = [
    "CampaignError",
    "SweepAxis",
    "FamilySpec",
    "CampaignSpec",
    "LaneInfo",
    "CompiledCampaign",
    "parse_campaign",
    "campaign_from_dict",
    "compile_campaign",
    "SWEEP_AXES",
]


class CampaignError(ValueError):
    """A campaign that cannot mean what it says (parse/compile time)."""


# the sampled axes a campaign may declare — each rides a TRACED leaf of a
# compiled plan, so sweeping it never changes a jit-static shape. Anything
# else is rejected by name with this list in the message.
SWEEP_AXES = (
    "phase.loss",
    "phase.delay",
    "phase.churn_leave",
    "phase.churn_join",
    "stream.rate",
    "control.lo",
    "control.hi",
    "control.target",
)

_DISTS = ("uniform", "linspace", "choice")

_BASE_KEYS = {
    "peers", "rounds", "slots", "fanout", "mode", "graph", "gamma", "m",
    "origins", "graph_seed", "forward_once", "sir_recover", "churn_leave",
    "churn_join", "rewire_slots", "coverage_target", "target_ratio",
    "stream_rate", "slot_ttl", "stream_origins", "stream_hashes",
    "control", "control_lo", "control_hi", "refresh_every",
    "grow", "grow_rate", "grow_capacity",
    "quorum_k", "suspicion_window", "accusation_budget",
}


@dataclasses.dataclass(frozen=True)
class SweepAxis:
    """One sampled axis of a family: ``axis`` ∈ :data:`SWEEP_AXES`."""

    axis: str
    dist: str  # "uniform" | "linspace" | "choice"
    lo: float = 0.0
    hi: float = 0.0
    values: tuple[float, ...] = ()
    phase: str | None = None  # phase.* axes: scope to one named phase

    def sample(self, n: int, rng: np.random.Generator) -> np.ndarray:
        if self.dist == "uniform":
            v = rng.uniform(self.lo, self.hi, size=n)
        elif self.dist == "linspace":
            v = np.linspace(self.lo, self.hi, num=n)
        else:  # choice: cycle deterministically over values
            v = np.asarray(
                [self.values[i % len(self.values)] for i in range(n)],
                dtype=float,
            )
        if self.axis in ("control.lo", "control.hi"):
            # bounds are integers: round AT SAMPLING time so the value a
            # lane's report/frontier groups by IS the bound its
            # controller ran with (not an unrounded float the compiler
            # would silently round)
            v = np.rint(v)
        return v


@dataclasses.dataclass(frozen=True)
class FamilySpec:
    """One scenario family: a catalogue entry plus its sampled axes.

    ``scenario`` is a path to a scenarios/*.toml, or (library/test use)
    an inline scenario dict in the ``scenario_from_dict`` surface, or
    ``None`` for a fault-free family.
    """

    name: str
    scenario: str | dict | None
    seeds: int
    sweeps: tuple[SweepAxis, ...] = ()

    @property
    def scenario_label(self) -> str | None:
        """Report-facing label: the path, or an inline dict's name."""
        if isinstance(self.scenario, dict):
            return str(self.scenario.get("name", "inline"))
        return self.scenario


@dataclasses.dataclass(frozen=True)
class CampaignSpec:
    """A parsed, not-yet-compiled campaign.

    ``root`` is the campaign file's directory (when parsed from a file):
    family scenario paths resolve against the working directory first,
    then against ``root`` and its parents — so a campaign under
    ``scenarios/campaigns/`` can name ``scenarios/lossy_links.toml``
    repo-relative and still compile from any cwd.
    """

    name: str
    seed: int
    base: dict
    families: tuple[FamilySpec, ...]
    root: str | None = None

    @property
    def n_lanes(self) -> int:
        return sum(f.seeds for f in self.families)


@dataclasses.dataclass(frozen=True)
class LaneInfo:
    """Host-side metadata of one compiled lane (report bookkeeping)."""

    index: int
    family: str
    seed_index: int  # lane's index inside its family
    sampled: dict  # axis -> sampled value


@dataclasses.dataclass
class CompiledCampaign:
    """K per-swarm plans stacked into one batched pytree.

    ``states``/``scenario``/``growth``/``stream``/``control`` carry a
    leading lane axis on every array leaf (static fields shared — the
    shared-static-shape rule); ``lane(k)`` extracts one lane's solo plans
    for the bit-identity cross-check and the ``--solo`` CLI path.
    """

    name: str
    k: int
    cfg: object  # SwarmConfig (jit-static, shared by every lane)
    rounds: int
    coverage_target: float
    target_ratio: float
    states: object  # batched SwarmState
    scenario: object | None  # batched CompiledScenario
    growth: object | None  # batched CompiledGrowth (identical lanes)
    stream: object | None  # batched CompiledStream
    control: object | None  # batched ControlSpec
    lanes: tuple[LaneInfo, ...]
    families: tuple[FamilySpec, ...]
    base: dict
    # the quorum-detector spec (kernels/liveness.py) is jit-STATIC and
    # hashable, so it is shared by every lane rather than stacked — the
    # shared-static-shape rule's degenerate case
    liveness: object | None = None  # QuorumSpec (static, lane-shared)
    # set by run_campaign(keep_states=False): the initial states were
    # DONATED and self.states now holds the FINAL states — lane
    # extraction would silently hand out post-run state, so it refuses
    consumed: bool = False

    def lane(self, k: int):
        """(state, scenario, growth, stream, control) of lane ``k`` —
        exactly the plans the batched program runs for that lane, so a
        solo ``simulate`` over them is the conformance oracle."""
        from tpu_gossip.core.state import lane_state

        if self.consumed:
            raise CampaignError(
                "campaign states were donated by run_campaign("
                "keep_states=False) and now hold the FINAL states — "
                "extract lanes before the donating run, or run with "
                "keep_states=True"
            )
        if not 0 <= k < self.k:
            raise CampaignError(f"lane {k} outside [0, {self.k})")
        # lane_state works on any stacked pytree, plans included
        pick = lambda p: None if p is None else lane_state(p, k)  # noqa: E731
        return (
            lane_state(self.states, k),
            pick(self.scenario),
            pick(self.growth),
            pick(self.stream),
            pick(self.control),
        )


# ------------------------------------------------------------- the parser
def _toml_tables(text: str) -> tuple[dict, dict, list[dict]]:
    """(campaign, base, families) from the campaign TOML subset.

    Same restricted reader family as faults/scenario.py (Python 3.10
    container, no stdlib tomllib): ``[campaign]``/``[base]`` tables,
    ``[[family]]`` entries, nested ``[[family.sweep]]`` attaching to the
    most recent family.
    """
    campaign: dict = {}
    base: dict = {}
    families: list[dict] = []
    cur: dict | None = None
    for lineno, raw in enumerate(text.splitlines(), 1):
        line = _strip_comment(raw).strip()
        if not line:
            continue
        if line == "[campaign]":
            cur = campaign
        elif line == "[base]":
            cur = base
        elif line == "[[family]]":
            cur = {"sweeps": []}
            families.append(cur)
        elif line == "[[family.sweep]]":
            if not families:
                raise CampaignError(
                    f"line {lineno}: [[family.sweep]] before any [[family]]"
                )
            cur = {}
            families[-1]["sweeps"].append(cur)
        elif line.startswith("["):
            raise CampaignError(
                f"line {lineno}: unknown table {line!r} (campaign files "
                "hold [campaign], [base], [[family]] and [[family.sweep]])"
            )
        else:
            key, eq, value = line.partition("=")
            if not eq:
                raise CampaignError(f"line {lineno}: expected key = value")
            if cur is None:
                raise CampaignError(f"line {lineno}: key outside any table")
            try:
                cur[key.strip()] = _parse_value(value)
            except ScenarioError as e:
                raise CampaignError(f"line {lineno}: {e}") from None
    return campaign, base, families


def campaign_from_dict(d: dict, root: str | None = None) -> CampaignSpec:
    """Build a spec from a plain dict (the TOML surface, for library use):
    ``{"name", "seed", "base": {...}, "families": [{...}, ...]}``."""
    base = dict(d.get("base", {}))
    unknown = set(base) - _BASE_KEYS
    if unknown:
        raise CampaignError(
            f"[base]: unknown keys {sorted(unknown)} (known: "
            f"{sorted(_BASE_KEYS)})"
        )
    families = []
    for i, f in enumerate(d.get("families", ())):
        unknown = set(f) - {"name", "scenario", "seeds", "sweeps"}
        if unknown:
            raise CampaignError(
                f"family {i}: unknown keys {sorted(unknown)}"
            )
        sweeps = []
        for j, s in enumerate(f.get("sweeps", ())):
            where = f"family {i} sweep {j}"
            axis = s.get("axis")
            if axis not in SWEEP_AXES:
                raise CampaignError(
                    f"{where}: unknown sampled axis {axis!r} — a campaign "
                    "can sample only axes that ride traced leaves (shared "
                    f"static shapes across the batch): {list(SWEEP_AXES)}"
                )
            dist = s.get("dist", "uniform")
            if dist not in _DISTS:
                raise CampaignError(
                    f"{where}: unknown dist {dist!r}; choose from {_DISTS}"
                )
            if dist == "choice":
                vals = tuple(float(v) for v in s.get("values", ()))
                if not vals:
                    raise CampaignError(f"{where}: choice needs values = [...]")
                if axis.startswith("phase.") and not all(
                    0.0 <= v <= 1.0 for v in vals
                ):
                    raise CampaignError(
                        f"{where}: {axis} samples a probability — every "
                        "value must lie in [0, 1] (the report groups lanes "
                        "by the sampled value, so an out-of-range sample "
                        "would misreport what actually ran)"
                    )
                sweeps.append(SweepAxis(axis=axis, dist=dist, values=vals,
                                        phase=s.get("phase")))
            else:
                if "lo" not in s or "hi" not in s:
                    raise CampaignError(f"{where}: {dist} needs lo and hi")
                lo, hi = float(s["lo"]), float(s["hi"])
                if hi < lo:
                    raise CampaignError(f"{where}: lo {lo} > hi {hi}")
                if axis.startswith("phase.") and not (
                    0.0 <= lo and hi <= 1.0
                ):
                    raise CampaignError(
                        f"{where}: {axis} samples a probability — lo/hi "
                        f"[{lo}, {hi}] must lie inside [0, 1] (the report "
                        "groups lanes by the sampled value, so a clamped "
                        "sample would misreport what actually ran)"
                    )
                sweeps.append(SweepAxis(axis=axis, dist=dist, lo=lo, hi=hi,
                                        phase=s.get("phase")))
        seeds = int(f.get("seeds", 0))
        if seeds < 1:
            raise CampaignError(
                f"family {i}: seeds must be >= 1 (got {seeds})"
            )
        families.append(FamilySpec(
            name=str(f.get("name", f"family{i}")),
            scenario=f.get("scenario"),
            seeds=seeds,
            sweeps=tuple(sweeps),
        ))
    spec = CampaignSpec(
        name=str(d.get("name", "campaign")),
        seed=int(d.get("seed", 0)),
        base=base,
        families=tuple(families),
        root=root,
    )
    if not spec.families:
        raise CampaignError("campaign declares no [[family]] entries")
    names = [f.name for f in spec.families]
    if len(names) != len(set(names)):
        dup = sorted({n for n in names if names.count(n) > 1})
        raise CampaignError(
            f"duplicate family names {dup} — lanes, scenarios and report "
            "blocks are grouped by family name, so duplicates would "
            "cross-wire them"
        )
    if spec.n_lanes < 2:
        raise CampaignError(
            f"campaign has {spec.n_lanes} lane — a one-lane campaign is a "
            "solo run (use run_sim --scenario); declare seeds >= 2 total"
        )
    if int(base.get("rounds", 0)) <= 0:
        raise CampaignError(
            "[base] needs rounds > 0 — campaigns run fixed horizons (the "
            "certification report reads per-round stats)"
        )
    return spec


def parse_campaign(source: str | Path) -> CampaignSpec:
    """Parse a campaign TOML file (or TOML text containing a newline)."""
    if isinstance(source, str) and "\n" in source:
        text, root = str(source), None
    else:
        text, root = Path(source).read_text(), str(Path(source).parent)
    campaign, base, families = _toml_tables(text)
    return campaign_from_dict({
        "name": campaign.get("name", "campaign"),
        "seed": campaign.get("seed", 0),
        "base": base,
        "families": families,
    }, root=root)


# ----------------------------------------------------------- the compiler
def _sample_lanes(spec: CampaignSpec) -> list[LaneInfo]:
    """Deterministic per-lane axis values: each (family, axis) draws from
    ``default_rng([campaign_seed, family_idx, axis_idx])`` — edits to one
    family never move another family's samples."""
    lanes: list[LaneInfo] = []
    idx = 0
    for fi, fam in enumerate(spec.families):
        values = {}
        for ai, ax in enumerate(fam.sweeps):
            rng = np.random.default_rng([spec.seed, fi, ai])
            values[ax.axis] = ax.sample(fam.seeds, rng)
        for si in range(fam.seeds):
            lanes.append(LaneInfo(
                index=idx, family=fam.name, seed_index=si,
                sampled={a: float(v[si]) for a, v in values.items()},
            ))
            idx += 1
    return lanes


def _override_phases(sdict: dict, axis: SweepAxis, value: float) -> None:
    """Apply a sampled phase-parameter to a scenario dict (in place).

    Scoped to ``axis.phase`` when named, else to every phase that
    DECLARES the parameter (> 0) — a lane cannot silently turn a fault
    class on in a phase its family never wrote, which would flip a
    static ``has_*`` flag mid-batch.
    """
    param = axis.axis.split(".", 1)[1]
    hits = 0
    for p in sdict["phases"]:
        if axis.phase is not None and p.get("name") != axis.phase:
            continue
        if axis.phase is None and not p.get(param, 0.0):
            continue
        # parse-time validation bounds samples to [0, 1]; the clip is
        # belt-and-braces so a float-rounding edge can't escape a
        # probability's domain
        p[param] = float(np.clip(value, 0.0, 1.0))
        hits += 1
    if hits == 0:
        where = (
            f"phase {axis.phase!r}" if axis.phase is not None
            else f"any phase declaring {param!r}"
        )
        raise CampaignError(
            f"sweep axis {axis.axis!r} matched no phase — the scenario "
            f"has no {where} (sampling it would flip a static has_* flag "
            "mid-batch)"
        )


def _scenario_dict(path: str, root: str | None) -> dict:
    """A scenario file as the dict surface ``scenario_from_dict`` takes,
    so sampled phase parameters can be overridden before compiling.
    Relative paths try the cwd first, then the campaign file's directory
    and its parents (a campaign under scenarios/campaigns/ names its
    families repo-relative)."""
    from tpu_gossip.faults.scenario import _toml_tables as _scenario_tables

    candidates = [Path(path)]
    if root is not None and not Path(path).is_absolute():
        r = Path(root)
        candidates += [r / path, r.parent / path, r.parent.parent / path]
    for c in candidates:
        if c.is_file():
            text = c.read_text()
            break
    else:
        raise CampaignError(
            f"family scenario {path!r}: no such file (tried "
            f"{[str(c) for c in candidates]})"
        )
    scenario, phases = _scenario_tables(text)
    return {"name": scenario.get("name", "scenario"), "phases": phases}


def _unify_scenarios(compiled: list, name: str):
    """Pad per-lane compiled scenarios to ONE static structure.

    Phase tables zero-pad to the widest phase count (padded rows are
    quiescent and ``phase_of_round`` never names them), ``has_*`` flags
    become the OR across lanes (a lane without the class runs its
    machinery over zero tables — value-identical to not running it, the
    quiescent-scenario contract), and ``join_burst`` unifies to a zero
    table on lanes without admission waves. Returns the per-lane list
    re-built with the shared structure.
    """
    import dataclasses as _dc

    import jax.numpy as jnp

    p_max = max(c.loss.shape[0] for c in compiled)
    flags = {
        f: any(getattr(c, f) for c in compiled)
        for f in ("has_partition", "has_blackout", "has_churn",
                  "has_loss_delay", "has_join_burst", "has_accusers",
                  "has_forgers", "has_floods")
    }
    # the static draw widths unify to the batch maximum (per-phase traced
    # fanouts stay the lane's own — columns past them are masked)
    statics = {
        f: max(getattr(c, f) for c in compiled)
        for f in ("max_forge_fanout", "max_flood_fanout")
    }

    def pad1(a, rows):
        return jnp.concatenate([
            a, jnp.zeros((rows - a.shape[0],) + a.shape[1:], dtype=a.dtype)
        ]) if a.shape[0] < rows else a

    def unify_opt(c, field, flag, n_cols=None, dtype=jnp.int32):
        if not flags[flag]:
            return None
        a = getattr(c, field)
        if a is None:
            shape = (c.loss.shape[0],) if n_cols is None else (
                c.loss.shape[0], n_cols)
            a = jnp.zeros(shape, dtype=dtype)
        return pad1(a, p_max)

    out = []
    for c in compiled:
        n_cols = c.burst.shape[1]
        out.append(_dc.replace(
            c,
            loss=pad1(c.loss, p_max), delay=pad1(c.delay, p_max),
            leave=pad1(c.leave, p_max), join=pad1(c.join, p_max),
            burst=pad1(c.burst, p_max), blackout=pad1(c.blackout, p_max),
            group_b=pad1(c.group_b, p_max),
            join_burst=unify_opt(c, "join_burst", "has_join_burst"),
            accuser=unify_opt(c, "accuser", "has_accusers", n_cols, bool),
            forger=unify_opt(c, "forger", "has_forgers", n_cols, bool),
            flooder=unify_opt(c, "flooder", "has_floods", n_cols, bool),
            forge_fanout=unify_opt(c, "forge_fanout", "has_forgers"),
            flood_fanout=unify_opt(c, "flood_fanout", "has_floods"),
            name=name,
            **flags,
            **statics,
        ))
    return out


def _check_lane_structures(plans: list, what: str) -> None:
    """The shared-static-shape backstop: every lane's compiled plan must
    match lane 0's pytree structure AND leaf shapes/dtypes — a mismatch
    would change a jit-static property mid-batch and can never reach
    ``vmap``. Raises :class:`CampaignError` naming the first divergence.
    """
    import jax

    ref = plans[0]
    ref_paths = jax.tree.structure(ref)
    ref_leaves = jax.tree.leaves(ref)
    for k, p in enumerate(plans[1:], 1):
        if jax.tree.structure(p) != ref_paths:
            raise CampaignError(
                f"{what}: lane {k}'s plan structure differs from lane 0's "
                "— the lanes disagree on a static field (shared-static-"
                "shape rule; every lane must compile to one structure)"
            )
        for a, b in zip(ref_leaves, jax.tree.leaves(p)):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise CampaignError(
                    f"{what}: lane {k} materializes {b.shape}/{b.dtype} "
                    f"where lane 0 has {a.shape}/{a.dtype} — a static "
                    "shape changed across the batch"
                )


def _stack(plans: list):
    # stack_states is SwarmState-flavored in name only: it stacks any
    # list of same-structure pytrees — one stacking idiom, not two
    from tpu_gossip.core.state import stack_states

    return stack_states(plans)


def _clamped_control(spec, lo_k: int, hi_k: int):
    """A per-lane controller bound expressed over the GLOBAL spec's
    static table width: entries clamp into ``[lo_k, hi_k]``, so AIMD
    widening saturates at the lane's bound (levels past it repeat the
    bound's fanout) while the draw width — the static ``spec.hi`` — and
    the table length stay shared across the batch. The pull mix follows
    the clamped values; the stress rung keeps its anti-entropy bit."""
    import dataclasses as _dc

    import jax.numpy as jnp
    import numpy as np_

    tbl = np_.asarray(spec.fanout_table)
    clipped = np_.clip(tbl, lo_k, hi_k).astype(np_.int32)
    pull = clipped <= spec.base
    if spec.levels > (spec.hi - spec.lo + 1):  # stress rung present
        pull[-1] = True
    return _dc.replace(
        spec,
        fanout_table=jnp.asarray(clipped),
        pull_table=jnp.asarray(pull),
    )


def compile_campaign(spec: CampaignSpec):
    """Compile a validated campaign into a :class:`CompiledCampaign`.

    Builds the shared topology once (topology is campaign-static: a
    per-lane graph would move the edge count D — a static shape — so
    lane diversity comes from protocol RNG, fault parameters, traffic
    rates and controller bounds), compiles every lane's plans, unifies
    the scenario structure, enforces the shared-static-shape rule, and
    stacks everything into batched pytrees.
    """
    import jax

    from tpu_gossip.core import topology
    from tpu_gossip.core.state import SwarmConfig, init_swarm, stack_states

    b = spec.base
    n_peers = int(b.get("peers", 1000))
    rounds = int(b["rounds"])
    mode = str(b.get("mode", "push"))
    fanout = int(b.get("fanout", 3))
    attach_m = int(b.get("m", 3))
    grow = int(b.get("grow", 0))
    lanes = _sample_lanes(spec)
    k_lanes = len(lanes)

    # ------------------------------------------------ shared topology
    g_rng = np.random.default_rng(int(b.get("graph_seed", spec.seed)))
    kind = str(b.get("graph", "pa"))
    if kind == "pa":
        graph = topology.build_csr(
            n_peers,
            topology.preferential_attachment(n_peers, m=attach_m, rng=g_rng),
        )
    elif kind == "chung-lu":
        deg = topology.powerlaw_degree_sequence(
            n_peers, gamma=float(b.get("gamma", 2.5)), rng=g_rng
        )
        graph = topology.build_csr(n_peers, topology.configuration_model(
            deg, rng=g_rng))
    else:
        raise CampaignError(
            f"[base] graph {kind!r}: campaigns run the local engine over "
            "a host CSR ('pa' or 'chung-lu')"
        )

    exists = None
    growth = None
    rewire_slots = int(b.get("rewire_slots", 0))
    if grow:
        from tpu_gossip.growth import compile_growth, pad_graph_for_growth

        if grow <= n_peers:
            raise CampaignError(
                f"[base] grow {grow} must exceed peers {n_peers}"
            )
        capacity = int(b.get("grow_capacity", grow))
        if capacity < grow:
            raise CampaignError(
                f"[base] grow_capacity {capacity} below the target {grow}"
            )
        graph, exists = pad_graph_for_growth(graph, capacity)
        rewire_slots = max(rewire_slots, attach_m)
    n_slots = graph.n

    cfg = SwarmConfig(
        n_peers=n_slots,
        msg_slots=int(b.get("slots", 16)),
        fanout=fanout,
        mode=mode,
        forward_once=bool(b.get("forward_once", False)),
        sir_recover_rounds=int(b.get("sir_recover", 0)),
        churn_leave_prob=float(b.get("churn_leave", 0.0)),
        churn_join_prob=float(b.get("churn_join", 0.0)),
        rewire_slots=rewire_slots,
    )

    # ------------------------------------------------ per-lane scenarios
    from tpu_gossip.faults import compile_scenario, scenario_from_dict

    fam_dicts = {
        f.name: (
            f.scenario if isinstance(f.scenario, dict)
            else _scenario_dict(f.scenario, spec.root)
        ) if f.scenario else None
        for f in spec.families
    }
    with_s = [f for f in spec.families if f.scenario]
    if with_s and len(with_s) != len(spec.families):
        raise CampaignError(
            "families mix scenario and scenario-free lanes — the batch "
            "compiles ONE static structure; give every family a scenario "
            "(a quiescent one is free) or none"
        )
    scen_lanes = None
    max_jb = 0
    if with_s:
        import copy

        fam_by_name = {f.name: f for f in spec.families}
        scen_lanes = []
        for lane in lanes:
            sdict = copy.deepcopy(fam_dicts[lane.family])
            fam = fam_by_name[lane.family]
            for ax in fam.sweeps:
                if ax.axis.startswith("phase."):
                    _override_phases(sdict, ax, lane.sampled[ax.axis])
            try:
                sspec = scenario_from_dict(sdict)
                sspec.validate(total_rounds=rounds, n_peers=n_peers)
                if sspec.uses_join_burst and not grow:
                    raise CampaignError(
                        f"family {lane.family!r}: join_burst phases are "
                        "admission waves for a growing fleet; set [base] "
                        "grow (a lane cannot grow alone — capacity is a "
                        "static shape shared by the batch)"
                    )
                if sspec.uses_adversaries and not int(b.get("quorum_k", 0)):
                    raise CampaignError(
                        f"family {lane.family!r}: Byzantine adversary "
                        "phases (accusers/forgers/floods) need the "
                        "quorum-defense planes; set [base] quorum_k "
                        "(quorum_k = 1 reproduces the reference's "
                        "single-report purge)"
                    )
                max_jb = max(max_jb, sspec.max_join_burst)
                scen_lanes.append(compile_scenario(
                    sspec, n_peers=n_peers, n_slots=n_slots,
                    total_rounds=rounds,
                ))
            except ScenarioError as e:
                raise CampaignError(
                    f"family {lane.family!r} lane {lane.seed_index}: {e}"
                ) from None
        scen_lanes = _unify_scenarios(scen_lanes, spec.name)
        _check_lane_structures(scen_lanes, "scenario")

    # ------------------------------------------------ growth (shared plan)
    if grow:
        growth = compile_growth(
            n_initial=n_peers,
            target=grow,
            n_slots=n_slots,
            joins_per_round=int(
                b.get("grow_rate", 0)
                or max(1, -(-(grow - n_peers) // max(rounds // 2, 1)))
            ),
            attach_m=attach_m,
            max_join_burst=max_jb,
        )

    # ------------------------------------------------ per-lane streams
    from tpu_gossip.traffic import (
        StreamError, compile_stream, default_max_inject, min_feasible_ttl,
    )

    stream_lanes = None
    base_rate = float(b.get("stream_rate", 0.0))
    rate_axis = any(
        ax.axis == "stream.rate" for f in spec.families for ax in f.sweeps
    )
    if rate_axis and base_rate <= 0:
        raise CampaignError(
            "sweep axis 'stream.rate' needs a loaded [base] "
            "(stream_rate > 0) — the stream's static batch shape is "
            "shared by every lane"
        )
    slot_ttl = int(b.get("slot_ttl", 0))
    if base_rate > 0:
        feasible = min_feasible_ttl(n_peers, fanout, mode)
        if slot_ttl == 0:
            slot_ttl = 3 * feasible
        if slot_ttl < feasible:
            raise CampaignError(
                f"[base] slot_ttl {slot_ttl} below the feasible coverage "
                f"horizon (~{feasible} rounds) — every message would "
                "recycle before it could cover"
            )
        lane_rates = [
            float(lane.sampled.get("stream.rate", base_rate))
            for lane in lanes
        ]
        if min(lane_rates) < 0:
            raise CampaignError("sampled stream.rate went negative")
        origin_rows = (
            np.flatnonzero(np.asarray(exists)) if exists is not None
            else np.arange(n_peers)
        )
        # ONE static batch shape serves every sampled rate: max_inject
        # pins to the largest lane
        peak = max(lane_rates)
        try:
            shared_inject = default_max_inject(peak)
            stream_lanes = [
                compile_stream(
                    rate=r,
                    msg_slots=cfg.msg_slots,
                    ttl=slot_ttl,
                    origin_rows=origin_rows,
                    origins=str(b.get("stream_origins", "uniform")),
                    k_hashes=int(b.get("stream_hashes", 1)),
                    max_inject=shared_inject,
                )
                for r in lane_rates
            ]
        except StreamError as e:
            raise CampaignError(f"[base] stream: {e}") from None
        _check_lane_structures(stream_lanes, "stream")

    # ------------------------------------------------ per-lane control
    from tpu_gossip.control import ControlError, compile_control

    control_lanes = None
    ctl_target = float(b.get("control", 0.0))
    bound_axis = any(
        ax.axis in ("control.lo", "control.hi", "control.target")
        for f in spec.families for ax in f.sweeps
    )
    if bound_axis and ctl_target <= 0:
        raise CampaignError(
            "sweep axes control.* need an active [base] controller "
            "(control = TARGET_RATIO) — the fanout table's static width "
            "is shared by every lane"
        )
    if ctl_target > 0:
        lo_b = int(b.get("control_lo", 1))
        hi_b = int(b.get("control_hi", max(2 * fanout, fanout)))
        lane_bounds = []
        for lane in lanes:
            lo_k = int(round(lane.sampled.get("control.lo", lo_b)))
            hi_k = int(round(lane.sampled.get("control.hi", hi_b)))
            if not (1 <= lo_k <= fanout <= hi_k):
                raise CampaignError(
                    f"lane {lane.index} ({lane.family!r}): sampled bounds "
                    f"[{lo_k}, {hi_k}] must satisfy 1 <= lo <= fanout "
                    f"{fanout} <= hi — the policy must express the static "
                    "rate on every lane"
                )
            lane_bounds.append((lo_k, hi_k))
        lo_g = min(lo for lo, _ in lane_bounds)
        hi_g = max(hi for _, hi in lane_bounds)
        if cfg.rewire_slots > 0 and hi_g > cfg.rewire_slots:
            raise CampaignError(
                f"controller bound hi {hi_g} exceeds the re-wiring width "
                f"rewire_slots {cfg.rewire_slots} (raise rewire_slots or "
                "narrow the sweep)"
            )
        try:
            import dataclasses as _dc

            g_spec = compile_control(
                target_ratio=ctl_target, fanout=fanout, lo=lo_g, hi=hi_g,
                refresh_every=int(b.get("refresh_every", 0)),
                ttl=slot_ttl if base_rate > 0 else 0,
            )
        except ControlError as e:
            raise CampaignError(f"[base] control: {e}") from None
        import jax.numpy as jnp

        control_lanes = []
        for lane, (lo_k, hi_k) in zip(lanes, lane_bounds):
            c = _clamped_control(g_spec, lo_k, hi_k)
            t = float(lane.sampled.get("control.target", ctl_target))
            if not (0.0 < t <= 1.0):
                raise CampaignError(
                    f"lane {lane.index}: sampled control.target {t} "
                    "outside (0, 1]"
                )
            control_lanes.append(_dc.replace(
                c, target_ratio=jnp.asarray(t, dtype=jnp.float32)
            ))
        _check_lane_structures(control_lanes, "control")

    # ------------------------------------------------ quorum detector
    liveness = None
    if int(b.get("quorum_k", 0)):
        from tpu_gossip.kernels.liveness import compile_quorum

        try:
            liveness = compile_quorum(
                quorum_k=int(b["quorum_k"]),
                window=int(b.get("suspicion_window",
                                 2 * cfg.detect_period_rounds)),
                budget=int(b.get("accusation_budget", 3)),
            )
        except ValueError as e:
            raise CampaignError(f"[base] quorum: {e}") from None
        if liveness.window < cfg.detect_period_rounds:
            raise CampaignError(
                f"[base] suspicion_window {liveness.window} is shorter "
                f"than the detector sweep period "
                f"({cfg.detect_period_rounds} rounds — the PING grace): "
                "a suspicion would expire before its probe could refute"
            )
    elif any(b.get(k) for k in ("suspicion_window", "accusation_budget")):
        raise CampaignError(
            "[base] suspicion_window/accusation_budget shape the quorum "
            "detector; set quorum_k"
        )

    # ------------------------------------------------ per-lane states
    parent = jax.random.fold_in(
        jax.random.key(spec.seed), FLEET_STREAM_SALT
    )
    n_origins = int(b.get("origins", 1))
    states = []
    for lane in lanes:
        o_rng = np.random.default_rng([spec.seed, 0x0F1E, lane.index])
        origins = o_rng.choice(
            n_peers, size=min(n_origins, n_peers), replace=False
        )
        states.append(init_swarm(
            graph, cfg,
            key=jax.random.fold_in(parent, lane.index),
            origins=origins, exists=exists,
        ))

    return CompiledCampaign(
        name=spec.name,
        k=k_lanes,
        cfg=cfg,
        rounds=rounds,
        coverage_target=float(b.get("coverage_target", 0.99)),
        target_ratio=float(b.get("target_ratio", 0.9)),
        states=stack_states(states),
        scenario=None if scen_lanes is None else _stack(scen_lanes),
        growth=None if growth is None else _stack([growth] * k_lanes),
        stream=None if stream_lanes is None else _stack(stream_lanes),
        control=None if control_lanes is None else _stack(control_lanes),
        lanes=tuple(lanes),
        families=spec.families,
        base=dict(b),
        liveness=liveness,
    )
