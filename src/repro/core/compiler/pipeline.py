"""The staged compile pipeline: Netlist / member plans -> ExecutionPlan.

One explicit ``PassPipeline`` replaces the three divergent compile paths that
used to live inline in ``plan.py``:

    normalize -> elide_cse -> fuse -> level -> schedule -> liveness
              -> stream_table -> emit

* ``lower_netlist`` runs the full pipeline on a single ``Netlist`` (the
  ``compile_plan`` path; ``fuse=False`` turns the structural stages into
  no-ops so per-gate fault injection observes every intermediate stream).
* ``merge_plans`` merges already-lowered member plans level-by-level
  (cross-member type batching) and enters the SAME pipeline at the
  ``schedule`` stage — merged-bank and padded-template compilation share the
  single tail (schedule -> liveness -> stream_table -> emit) with the
  single-netlist path, so every ``ExecutionPlan``, merged or not, carries an
  Algorithm-1 ``Schedule``, a liveness scratch assignment, and a stream
  table built by the same stages.

Stages communicate through a mutable ``Lowering`` context; each stage is a
pure function of it, so alternative pipelines (e.g. a no-schedule variant for
tooling) are just different stage tuples.  Caching/interning stays in the
``repro.core.plan`` facade — the pipeline itself is stateless apart from the
process-wide ``serial`` stamp.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable

from .. import obs
from ..gates import Netlist, PrimaryInput
from .ir import (FUSED_MUX, FUSED_XOR, BankPlan, CompiledOp, ExecutionPlan,
                 build_stream_table, member_prefix)
from .stages import (_WGate, _WOp, _absorb_nots, _elide_and_cse, _find_mux_fusions,
                     _find_xor_fusions, _fold_ands, assign_liveness, level_ops,
                     schedule_passes)

# Monotone compile stamp shared by plans and banks (ExecutionPlan.serial /
# BankPlan.serial).  Deliberately NOT reset by plan.clear_cache(): serial
# order anchors bank-template canonical member order across cache epochs.
_SERIAL = itertools.count()


def next_serial() -> int:
    """Next process-wide compile stamp (plans, banks)."""
    return next(_SERIAL)


@dataclasses.dataclass
class Lowering:
    """Mutable compile context threaded through the pipeline stages.

    The front half (``source``, ``fuse``) is set by the entry point; each
    stage fills in its output fields; ``emit`` assembles the final
    ``ExecutionPlan`` into ``plan``.  The merge front-end pre-fills the
    leveled fields and runs only the shared tail stages.
    """

    name: str
    pis: tuple
    outputs: tuple[str, ...]
    state_pis: tuple[str, ...] = ()
    state_drivers: tuple[str, ...] = ()
    state_inits: tuple[float, ...] = ()
    fuse: bool = True
    n_gates: int = 0
    source: Netlist | None = None           # netlist front-end only
    # -- stage outputs ------------------------------------------------------
    protected: set = dataclasses.field(default_factory=set)
    work_gates: list = dataclasses.field(default_factory=list)
    alias: dict = dataclasses.field(default_factory=dict)
    ops: list = dataclasses.field(default_factory=list)
    levels: tuple = ()
    counters: dict = dataclasses.field(default_factory=lambda: {
        "buff_elided": 0, "cse_elided": 0, "mux_fused": 0,
        "xor_fused": 0, "and_fused": 0, "not_absorbed": 0})
    stream_table: Any = None
    schedule: Any = None
    max_live: int = 0
    pi_slots: tuple = ()
    plan: ExecutionPlan | None = None


# --------------------------------- stages ------------------------------------------

def stage_normalize(ctx: Lowering) -> None:
    """Validate the source netlist and snapshot the observable-node set."""
    net = ctx.source
    net.validate()
    ctx.n_gates = len(net.gates)
    ctx.protected = set(net.outputs) | {drv for drv, _
                                        in net.state_bindings.values()}
    if not ctx.fuse:
        # Per-gate fault injection must observe every intermediate stream:
        # no elision, no dedup, no fusion (mirrors the interpreter exactly).
        ctx.work_gates = [_WGate(g.gid, g.gtype, g.inputs, g.output)
                          for g in net.gates]


def stage_elide_cse(ctx: Lowering) -> None:
    """BUFF elision + structural CSE (rewrites the graph fusion will see)."""
    if not ctx.fuse:
        return
    gates, alias, n_buff, n_cse = _elide_and_cse(ctx.source.gates)
    # Only observable elided nodes (outputs / state drivers) need re-exposing
    # at execution time — every other use was rewritten to the survivor.
    # Restricting the recorded aliases to those keeps the next stage sound: a
    # dangling alias to a node fusion then absorbs would crash the re-expose
    # loop.
    alias = {s: d for s, d in alias.items() if s in ctx.protected}
    # An elided observable node aliases its survivor — which makes the
    # SURVIVOR observable too: resolve protection through the aliases so
    # pattern fusion cannot absorb a node some alias must re-expose.
    ctx.protected |= set(alias.values())
    ctx.work_gates = gates
    ctx.alias = alias
    ctx.counters["buff_elided"] = n_buff
    ctx.counters["cse_elided"] = n_cse


def stage_fuse(ctx: Lowering) -> None:
    """Pattern fusion (MUX/XOR) + NOT-directed cleanups (AND fold, absorb)."""
    if ctx.fuse:
        mux_roots, dead = _find_mux_fusions(ctx.work_gates, ctx.protected)
        xor_roots = _find_xor_fusions(ctx.work_gates, ctx.protected, dead)
    else:
        mux_roots, dead, xor_roots = {}, set(), {}
    # Materialize the post-pattern-fusion op list, then run the NOT-directed
    # cleanups on it.  Both run after the 4-gate matchers so the NOT-bearing
    # MUX/XOR forms are recognized first.
    ops: list[_WOp] = []
    for g in ctx.work_gates:
        if g.gid in dead:
            continue
        if g.gid in mux_roots:
            op, ins = FUSED_MUX, mux_roots[g.gid]
        elif g.gid in xor_roots:
            op, ins = FUSED_XOR, xor_roots[g.gid]
        else:
            op, ins = g.gtype, g.inputs
        ops.append(_WOp(g.gid, op, tuple(ins), (False,) * len(ins), g.output))
    if ctx.fuse:
        n_and = _fold_ands(ops, ctx.protected)
        n_not = _absorb_nots(ops, ctx.protected)
    else:
        n_and = n_not = 0
    ctx.ops = ops
    ctx.counters["mux_fused"] = len(mux_roots)
    ctx.counters["xor_fused"] = len(xor_roots)
    ctx.counters["and_fused"] = n_and
    ctx.counters["not_absorbed"] = n_not


def stage_level(ctx: Lowering) -> None:
    """Longest-path leveling with per-level (op, neg) type batching."""
    ctx.levels = level_ops(ctx.ops, (p.name for p in ctx.pis))


def stage_schedule(ctx: Lowering) -> None:
    """Algorithm 1 over the leveled passes (see ``stages.schedule_passes``)."""
    ctx.schedule = schedule_passes(ctx.name, ctx.pis, ctx.levels)


def stage_liveness(ctx: Lowering) -> None:
    """Last-use analysis + scratch-slot assignment over the leveled passes.

    Runs after ``schedule`` (the pass order is final) and before
    ``stream_table``, and — like both — on every compile path: single
    netlists, merged BankPlans, and padded templates all enter at or before
    this stage, so every ``ExecutionPlan`` carries ``max_live``/``pi_slots``
    and per-op ``slots``/``free_after``.  Observable nodes are protected
    through the alias map: an elided output's survivor must stay live for the
    executor's re-expose step.
    """
    observable = set(ctx.outputs) | set(ctx.state_drivers)
    protected = {ctx.alias.get(nm, nm) for nm in observable}
    ctx.levels, ctx.pi_slots, ctx.max_live = assign_liveness(
        ctx.levels, (p.name for p in ctx.pis), protected)


def stage_stream_table(ctx: Lowering) -> None:
    """Lay out the batched-SNG stream table over the plan's PIs."""
    ctx.stream_table = build_stream_table(ctx.pis)


def stage_emit(ctx: Lowering) -> None:
    """Assemble the frozen ExecutionPlan from the staged context."""
    c = ctx.counters
    ctx.plan = ExecutionPlan(
        name=ctx.name,
        pis=tuple(ctx.pis),
        n_gates=ctx.n_gates,
        levels=ctx.levels,
        outputs=tuple(ctx.outputs),
        state_pis=ctx.state_pis,
        state_drivers=ctx.state_drivers,
        state_inits=ctx.state_inits,
        fused=ctx.fuse,
        n_fused_mux=c["mux_fused"],
        stream_table=ctx.stream_table,
        aliases=tuple(sorted(ctx.alias.items())),
        n_fused_xor=c["xor_fused"],
        n_buff_elided=c["buff_elided"],
        n_cse_elided=c["cse_elided"],
        n_fused_and=c["and_fused"],
        n_not_absorbed=c["not_absorbed"],
        serial=next_serial(),
        schedule=ctx.schedule,
        max_live=ctx.max_live,
        pi_slots=ctx.pi_slots,
    )


@dataclasses.dataclass(frozen=True)
class PassPipeline:
    """An ordered tuple of named compile stages over a ``Lowering`` context.

    ``run(ctx)`` applies every stage in order; ``run(ctx, start=...)`` enters
    at a named stage (the merge front-end joins at ``"schedule"``).  Stage
    names are part of the public shape: tooling and tests address the
    pipeline by them.
    """

    stages: tuple[tuple[str, Callable[[Lowering], None]], ...]

    @property
    def stage_names(self) -> tuple[str, ...]:
        return tuple(name for name, _ in self.stages)

    def run(self, ctx: Lowering, start: str | None = None) -> ExecutionPlan:
        tr = obs.current_trace()
        started = start is None
        for name, fn in self.stages:
            started = started or name == start
            if started:
                if tr is None:
                    fn(ctx)
                else:
                    with tr.span(f"compile.{name}", plan=ctx.name):
                        fn(ctx)
        if not started:
            raise ValueError(f"unknown pipeline stage {start!r}; "
                             f"have {self.stage_names}")
        return ctx.plan


#: The one pipeline every compile path flows through.
DEFAULT_PIPELINE = PassPipeline((
    ("normalize", stage_normalize),
    ("elide_cse", stage_elide_cse),
    ("fuse", stage_fuse),
    ("level", stage_level),
    ("schedule", stage_schedule),
    ("liveness", stage_liveness),
    ("stream_table", stage_stream_table),
    ("emit", stage_emit),
))


# ------------------------------- entry points --------------------------------------

def lower_netlist(net: Netlist, fuse_mux: bool = True,
                  pipeline: PassPipeline | None = None) -> ExecutionPlan:
    """Lower one netlist through the full pipeline (uncached).

    The caching/interning front (per-instance memo + structure-keyed LRU)
    lives in the ``repro.core.plan`` facade; this is the pure compile.
    """
    state_items = sorted(net.state_bindings.items())
    ctx = Lowering(
        name=net.name,
        pis=tuple(net.pis),
        outputs=tuple(net.outputs),
        state_pis=tuple(s for s, _ in state_items),
        state_drivers=tuple(d for _, (d, _) in state_items),
        state_inits=tuple(i for _, (_, i) in state_items),
        fuse=fuse_mux,
        source=net,
    )
    return (pipeline or DEFAULT_PIPELINE).run(ctx)


def merge_plans(plans: "list[ExecutionPlan]", indices: "list[int]",
                name: str,
                pipeline: PassPipeline | None = None) -> ExecutionPlan:
    """Merge same-kind plans into one cross-member type-batched plan.

    ``indices`` are the members' caller-order positions — they become the node
    namespace prefixes, so the executor can scatter outputs back per member.
    Members are independent graphs, so each gate keeps its per-member level;
    merging level ``L`` across members and type-batching within it is a valid
    re-leveling of the union graph.  Gate ids are offset by the running gate
    count so they index a flat per-merge-order fault-key array.  Identity
    (padding) members contribute no nodes and are exempt from the kind check,
    so a padded bank template can carry them in either group.

    The merged levels re-enter the shared pipeline at the ``schedule`` stage:
    merged plans get their Algorithm-1 schedule and stream table from the
    same stages as single-netlist plans.  (The structural stages must NOT
    re-run here — members were optimized per-netlist, and cross-member
    rewrites would break the per-member key discipline's bit-identity.)
    """
    if len({p.is_sequential for p in plans if not p.is_identity}) > 1:
        raise ValueError("merge_plans: cannot mix sequential and "
                         "combinational members in one merged plan")
    prefixes = [member_prefix(i) for i in indices]
    offsets = []
    off = 0
    for p in plans:
        offsets.append(off)
        off += p.n_gates

    n_levels = max(len(p.levels) for p in plans)
    levels = []
    for lvl in range(n_levels):
        by_op: dict[tuple, list[tuple]] = {}
        for p, pre, goff in zip(plans, prefixes, offsets):
            if lvl >= len(p.levels):
                continue
            for cop in p.levels[lvl]:
                by_op.setdefault((cop.op, cop.neg), []).append((cop, pre, goff))
        ops = []
        for (op, neg), entries in by_op.items():
            arity = len(entries[0][0].inputs)
            ops.append(CompiledOp(
                op=op,
                gids=tuple(goff + g for cop, _, goff in entries
                           for g in cop.gids),
                inputs=tuple(tuple(pre + n for cop, pre, _ in entries
                                   for n in cop.inputs[j])
                             for j in range(arity)),
                outputs=tuple(pre + o for cop, pre, _ in entries
                              for o in cop.outputs),
                neg=neg,
            ))
        levels.append(tuple(ops))

    pis = tuple(dataclasses.replace(
        pi, name=pre + pi.name,
        corr_group=(pre + pi.corr_group) if pi.corr_group else None)
        for p, pre in zip(plans, prefixes) for pi in p.pis)
    # NOTE: the merged stream table is laid out over the *merged* PI list, so
    # its lanes differ from the members' own tables.  Bank execution generates
    # streams from each member's table with that member's key (preserving
    # merged == looped bit-identity); the merged table exists for plans
    # executed standalone.
    ctx = Lowering(
        name=name,
        pis=pis,
        outputs=tuple(pre + o for p, pre in zip(plans, prefixes)
                      for o in p.outputs),
        state_pis=tuple(pre + s for p, pre in zip(plans, prefixes)
                        for s in p.state_pis),
        state_drivers=tuple(pre + d for p, pre in zip(plans, prefixes)
                            for d in p.state_drivers),
        state_inits=tuple(i for p in plans for i in p.state_inits),
        # Identity padding members are vacuously "fused"; only real members
        # decide whether the merged plan admits per-gate fault injection.
        fuse=any(p.fused for p in plans if not p.is_identity),
        n_gates=off,
        levels=tuple(levels),
        counters={
            "buff_elided": sum(p.n_buff_elided for p in plans),
            "cse_elided": sum(p.n_cse_elided for p in plans),
            "mux_fused": sum(p.n_fused_mux for p in plans),
            "xor_fused": sum(p.n_fused_xor for p in plans),
            "and_fused": sum(p.n_fused_and for p in plans),
            "not_absorbed": sum(p.n_not_absorbed for p in plans),
        },
    )
    ctx.alias = {pre + a: pre + b for p, pre in zip(plans, prefixes)
                 for a, b in p.aliases}
    return (pipeline or DEFAULT_PIPELINE).run(ctx, start="schedule")


def build_bank(members: "tuple[ExecutionPlan, ...]",
               name: str | None = None) -> BankPlan:
    """Merge a member-plan tuple into a BankPlan (uncached).

    Splits members into the combinational and sequential merge groups and
    runs each through ``merge_plans`` (i.e. the shared pipeline tail).  The
    cache front lives in the ``repro.core.plan`` facade.
    """
    comb_idx = tuple(i for i, m in enumerate(members) if not m.is_sequential)
    seq_idx = tuple(i for i, m in enumerate(members) if m.is_sequential)
    bank_name = name or f"bank{len(members)}"
    comb = merge_plans([members[i] for i in comb_idx], list(comb_idx),
                       f"{bank_name}/comb") if comb_idx else None
    seq = merge_plans([members[i] for i in seq_idx], list(seq_idx),
                      f"{bank_name}/seq") if seq_idx else None
    return BankPlan(name=bank_name, members=members, comb=comb, seq=seq,
                    comb_members=comb_idx, seq_members=seq_idx,
                    serial=next_serial())


# ------------------------------- state-cone split ---------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class ConeSplit:
    """A sequential plan split at its state cone (``split_state_cone``).

    ``cone`` holds every batched gate whose transitive inputs include a
    state PI; ``hoisted`` (``None`` when no gate lies outside the cone)
    holds every other gate, a combinational plan over the stream PIs whose
    outputs are ``pre_outputs``.  ``boundary`` lists the nodes outside the
    cone that the cone reads per bit: stream PIs and hoisted outputs read
    by cone gates, and state drivers that are themselves hoisted.
    ``serial_outputs`` are the plan outputs the cone computes;
    ``taken_outputs`` pairs every other output with the node outside the
    cone that holds its stream.  ``n_hoisted``/``n_serial`` count the
    batched pass outputs on each side.
    """

    hoisted: ExecutionPlan | None
    cone: ExecutionPlan
    boundary: tuple[str, ...]
    pre_outputs: tuple[str, ...]
    serial_outputs: tuple[str, ...]
    taken_outputs: tuple[tuple[str, str], ...]
    n_hoisted: int
    n_serial: int


def split_state_cone(plan: ExecutionPlan) -> ConeSplit:
    """Split a sequential plan into its state cone and the gates before it.

    A gate outside the cone reads only stream PIs and other such gates.
    Every op is bitwise, so running those gates once over whole packed
    streams gives, at bit ``t`` of each word, the bit a per-bit run would
    give at step ``t``: only the cone has to run bit by bit.  The split is
    per batched gate, not per op, since a merged bank batches gates of both
    sides into one op.  Each half re-enters the pipeline at the
    ``liveness`` stage, so its ``slots``/``free_after`` are its own.
    """
    alias = dict(plan.aliases)
    cone_nodes = set(plan.state_pis)
    halves: tuple[list, list] = ([], [])          # hoisted, cone levels
    for level in plan.levels:
        ops: tuple[list, list] = ([], [])
        for cop in level:
            in_cone = [any(row[i] in cone_nodes for row in cop.inputs)
                       for i in range(cop.n_batched)]
            for side, side_ops in enumerate(ops):
                idx = [i for i, c in enumerate(in_cone) if c == bool(side)]
                if idx:
                    side_ops.append(CompiledOp(
                        op=cop.op, gids=tuple(cop.gids[i] for i in idx),
                        inputs=tuple(tuple(row[i] for i in idx)
                                     for row in cop.inputs),
                        outputs=tuple(cop.outputs[i] for i in idx),
                        neg=cop.neg))
            cone_nodes.update(o for o, c in zip(cop.outputs, in_cone) if c)
        for levels, side_ops in zip(halves, ops):
            if side_ops:
                levels.append(tuple(side_ops))
    hoisted_levels, cone_levels = halves

    def outside(names) -> tuple[str, ...]:
        return tuple(dict.fromkeys(n for n in names if n not in cone_nodes))

    serial = tuple(o for o in plan.outputs if alias.get(o, o) in cone_nodes)
    taken = tuple((o, alias.get(o, o)) for o in plan.outputs
                  if alias.get(o, o) not in cone_nodes)
    boundary = outside([n for level in cone_levels for cop in level
                        for row in cop.inputs for n in row]
                       + [alias.get(d, d) for d in plan.state_drivers])
    pre_outputs = outside([*boundary, *(src for _, src in taken)])

    def n_outputs(levels) -> int:
        return sum(cop.n_batched for level in levels for cop in level)

    hoisted = None
    if hoisted_levels:
        hoisted = DEFAULT_PIPELINE.run(Lowering(
            name=f"{plan.name}/hoisted",
            pis=tuple(p for p in plan.pis if p.name not in plan.state_pis),
            outputs=pre_outputs, fuse=plan.fused,
            n_gates=n_outputs(hoisted_levels),
            levels=tuple(hoisted_levels)), start="liveness")
    observable = {*serial, *plan.state_drivers}
    cone_ctx = Lowering(
        name=f"{plan.name}/cone",
        pis=(*(p for p in plan.pis if p.name in plan.state_pis),
             *(PrimaryInput(b) for b in boundary)),
        outputs=serial, state_pis=plan.state_pis,
        state_drivers=plan.state_drivers, state_inits=plan.state_inits,
        fuse=plan.fused, n_gates=n_outputs(cone_levels),
        levels=tuple(cone_levels))
    cone_ctx.alias = {s: d for s, d in plan.aliases if s in observable}
    return ConeSplit(
        hoisted=hoisted,
        cone=DEFAULT_PIPELINE.run(cone_ctx, start="liveness"),
        boundary=boundary, pre_outputs=pre_outputs, serial_outputs=serial,
        taken_outputs=taken, n_hoisted=n_outputs(hoisted_levels),
        n_serial=n_outputs(cone_levels))
