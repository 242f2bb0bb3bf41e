"""Plan compilation facade: caching front over the staged compiler pipeline.

The actual lowering lives in ``repro.core.compiler`` — a typed IR
(``compiler/ir.py``), individual stages (``compiler/stages.py``), and the
staged ``PassPipeline`` (``compiler/pipeline.py``) through which ALL compile
paths flow:

  * ``compile_plan``          — one netlist, full pipeline;
  * ``compile_bank_plan``     — N netlists, member plans merged level-wise,
                                re-entering the pipeline at the schedule stage;
  * ``compile_bank_template`` / ``compile_bank_members`` — the padded
                                canonical serving layout, same merge path.

This module is the public import surface (external code must not import
``repro.core.compiler`` internals — ruff TID251 enforces it) plus the state
the pipeline deliberately doesn't own:

  * the structure-keyed LRU plan/bank caches (interning: equal structures
    return the *same* plan object, which keys the downstream jit cache);
  * the per-netlist ``_plan_memo`` fast path, epoch-guarded so
    ``clear_cache()`` invalidates memoized plans too;
  * cumulative optimizer provenance counters (``cache_info()``).

Plans are cached per netlist *structure* (PIs, gates, outputs, state
bindings), so repeated executions of equal circuits — every benchmark/test
pattern — hit both the plan cache and the downstream jit cache.
"""
from __future__ import annotations

import weakref
from collections import OrderedDict, defaultdict

from .compiler.ir import (_COMMUTATIVE, _OP_ARITY, FUSED_MUX, FUSED_XOR,  # noqa: F401
                          IDENTITY_NAME, BankPlan, CompiledOp, ExecutionPlan,
                          StreamTable, build_stream_table, member_prefix)
from .compiler.pipeline import (DEFAULT_PIPELINE, ConeSplit,  # noqa: F401
                                PassPipeline, build_bank, lower_netlist,
                                merge_plans, next_serial, split_state_cone)
from .compiler.stages import signature as _signature  # noqa: F401
from .gates import Netlist

# ----------------------------------- caches ----------------------------------------

# Both structural caches are LRU-bounded: serving traffic compiles a new
# plan/bank per *bucket shape*, and an unbounded dict would grow with every
# distinct member set ever seen.  Eviction only drops interning — an evicted
# structure recompiles to a fresh (bit-identical) plan on next use — so the
# caps trade recompiles for memory, never correctness.
_PLAN_CACHE: "OrderedDict[tuple, ExecutionPlan]" = OrderedDict()
_BANK_CACHE: "OrderedDict[tuple, BankPlan]" = OrderedDict()
_CACHE_CAPS = {"plans": 1024, "banks": 256}
_EVICTIONS = {"plan_evictions": 0, "bank_evictions": 0}
# Cumulative optimizer counters across cache-missing compiles (reported by
# cache_info so perf work can see how many nodes the structural passes
# removed, and reset by clear_cache).
_OPT_COUNTS = {"buff_elided": 0, "cse_elided": 0, "mux_fused": 0,
               "xor_fused": 0, "and_fused": 0, "not_absorbed": 0}
# Cache generation stamp: bumped by clear_cache() and baked into every
# per-netlist memo key, so memoized plans from before a clear can never be
# served after it (they'd resurrect cleared interning).
_CACHE_EPOCH = [0]


def _cache_get(cache: OrderedDict, key):
    hit = cache.get(key)
    if hit is not None:
        cache.move_to_end(key)
    return hit


def _cache_put(cache: OrderedDict, key, value, cap_key: str,
               evict_key: str) -> None:
    cache[key] = value
    cache.move_to_end(key)
    while len(cache) > _CACHE_CAPS[cap_key]:
        cache.popitem(last=False)
        _EVICTIONS[evict_key] += 1


def set_cache_caps(plans: int | None = None,
                   banks: int | None = None) -> dict[str, int]:
    """Set the LRU caps (entries) of the plan/bank caches; returns the caps.

    Shrinking a cap evicts least-recently-used entries immediately (counted
    in ``cache_info()['plan_evictions'/'bank_evictions']``).
    """
    if plans is not None:
        _CACHE_CAPS["plans"] = int(plans)
        while len(_PLAN_CACHE) > _CACHE_CAPS["plans"]:
            _PLAN_CACHE.popitem(last=False)
            _EVICTIONS["plan_evictions"] += 1
    if banks is not None:
        _CACHE_CAPS["banks"] = int(banks)
        while len(_BANK_CACHE) > _CACHE_CAPS["banks"]:
            _BANK_CACHE.popitem(last=False)
            _EVICTIONS["bank_evictions"] += 1
    return dict(_CACHE_CAPS)


def cache_info() -> dict[str, int]:
    return {"plans": len(_PLAN_CACHE), "banks": len(_BANK_CACHE),
            "plan_cap": _CACHE_CAPS["plans"], "bank_cap": _CACHE_CAPS["banks"],
            **_EVICTIONS, **_OPT_COUNTS}


def clear_cache() -> None:
    """Drop all structural caches AND invalidate per-netlist plan memos.

    The memos live on Netlist instances, so they can't be cleared here
    directly; instead the cache epoch is baked into every memo key — bumping
    it makes every existing memo entry unreachable (and ``compile_plan``
    prunes stale-epoch entries on its next visit to each netlist).
    """
    _PLAN_CACHE.clear()
    _BANK_CACHE.clear()
    for k in _OPT_COUNTS:
        _OPT_COUNTS[k] = 0
    for k in _EVICTIONS:
        _EVICTIONS[k] = 0
    _CACHE_EPOCH[0] += 1


# -------------------------------- compilation -------------------------------------

def compile_plan(net: Netlist, fuse_mux: bool = True) -> ExecutionPlan:
    """Compile ``net`` into an ExecutionPlan (structure-cached).

    Runs the full staged pipeline (``compiler.DEFAULT_PIPELINE``): normalize
    → elide_cse → fuse → level → schedule → liveness → stream_table → emit
    (see docs/ARCHITECTURE.md for what each stage does).

    Compilation is key-free: the plan fixes each stream PI's *key lane* in
    its stream table, but randomness is only drawn at execution time from
    the request's own PRNG key — one structure compiles once and serves any
    number of keys.

    Example::

        net = circuits.sc_multiply()
        p = compile_plan(net)
        p.n_gates, p.n_passes, p.max_live      # provenance + liveness
        executor.execute_value(net, {"a": 0.5, "b": 0.5},
                               jax.random.key(0), 256)  # runs this plan

    ``fuse_mux=False`` keeps every gate as its own batched op, disabling ALL
    structural optimization (MUX/XOR fusion, BUFF elision, CSE) — required
    when per-gate fault injection must observe the intermediate streams
    (Table 4), and by construction bit-identical to the interpreter in all
    cases.  The optimized default is bit-identical too (every pass is an
    exact stream identity); only the per-gate injection points differ.

    A fast per-instance memo front-runs the structural cache so the hot
    execute() path doesn't rebuild the signature every call.  The memo is
    guarded by the netlist's mutation counter (bumped by every Netlist
    mutator, including in-place ``replace_gate`` edits that leave the gate
    count unchanged) plus the PI/gate counts as a belt-and-braces check, so
    mutating a compiled netlist through its mutators always recompiles — and
    by the cache epoch, so ``clear_cache()`` invalidates memos too.
    """
    memo = net.__dict__.setdefault("_plan_memo", {})
    memo_key = (_CACHE_EPOCH[0], fuse_mux, getattr(net, "_version", None),
                len(net.pis), len(net.gates))
    hit = memo.get(memo_key)
    if hit is not None:
        return hit

    # Entries from older netlist versions or cache epochs can never hit again
    # — drop them so a mutate/recompile (or clear/recompile) loop doesn't grow
    # the memo (at most the two fuse_mux variants of the current version
    # remain).
    stale = [k for k in memo
             if k[0] != memo_key[0] or k[2] != memo_key[2]]
    for k in stale:
        del memo[k]

    key = (_signature(net), fuse_mux)
    cached = _cache_get(_PLAN_CACHE, key)
    if cached is not None:
        memo[memo_key] = cached
        return cached

    plan = lower_netlist(net, fuse_mux=fuse_mux)
    _OPT_COUNTS["buff_elided"] += plan.n_buff_elided
    _OPT_COUNTS["cse_elided"] += plan.n_cse_elided
    _OPT_COUNTS["mux_fused"] += plan.n_fused_mux
    _OPT_COUNTS["xor_fused"] += plan.n_fused_xor
    _OPT_COUNTS["and_fused"] += plan.n_fused_and
    _OPT_COUNTS["not_absorbed"] += plan.n_not_absorbed
    _cache_put(_PLAN_CACHE, key, plan, "plans", "plan_evictions")
    memo[memo_key] = plan
    return plan


# ---------------------------- bank-level merging -----------------------------------
#
# The paper's Fig. 8 bank executes many circuit instances side by side: every
# subarray pass fires the same gate type across ALL columns of ALL subarrays,
# so independent circuits mapped to disjoint columns share passes.  The TPU
# translation: merge N (possibly different) netlists' plans into ONE plan
# whose levels type-batch gates *across* members — one CompiledOp pass covers
# every same-type gate of a level bank-wide, and N app instances execute as a
# single fused XLA program (executor.execute_many).  The merge itself is
# ``compiler.pipeline.merge_plans`` / ``build_bank``; this layer adds caching.


def _cached_bank(members: "tuple[ExecutionPlan, ...]", key: tuple,
                 name: str | None) -> BankPlan:
    """Merge a member-plan tuple into a (cached) BankPlan under ``key``."""
    cached = _cache_get(_BANK_CACHE, key)
    if cached is not None:
        return cached
    bank = build_bank(members, name)
    _cache_put(_BANK_CACHE, key, bank, "banks", "bank_evictions")
    return bank


def compile_bank_plan(nets: "list[Netlist]", fuse_mux: bool = True,
                      name: str | None = None) -> BankPlan:
    """Compile N netlists into one bank-level plan (cached).

    Members may repeat (N instances of one circuit) and mix combinational and
    sequential netlists; equal structures intern to the same member plan, so
    the cache key is the member-plan identity tuple.  ``fuse_mux=False``
    compiles combinational members unfused (per-gate fault injection);
    sequential members always fuse — their injection points are PI/output
    streams, outside the plan (mirroring ``executor._plan_for``).

    Member ``i`` of the bank draws its streams from request ``i``'s key
    exactly as a standalone execute would, so merged execution is
    bit-identical to a loop of per-member calls.

    Example::

        nets = [circuits.sc_multiply(), circuits.sc_sqrt()]
        bank = compile_bank_plan(nets)
        bank.n_passes, bank.n_passes_looped    # cross-member pass sharing
        executor.run([executor.ExecRequest(n, v, k, opts)
                      for n, v, k in zip(nets, values, keys)])  # one dispatch
    """
    if not nets:
        raise ValueError("compile_bank_plan: need at least one netlist")
    members = tuple(compile_plan(n, fuse_mux=fuse_mux or n.is_sequential)
                    for n in nets)
    return _cached_bank(members, (members, fuse_mux), name)


# --------------------------- canonical bank templates ------------------------------
#
# Serving traffic cannot afford a fresh BankPlan (and jit trace) per request
# set: the member multiset changes every arrival.  A *bank template* is the
# canonical padded form of a request multiset — distinct member structures in
# deterministic (compile-serial) order, each structure's slot count rounded up
# to a power of two, optionally topped up with no-op identity members to a
# fixed total — so every request set that fits a bucket reuses ONE BankPlan
# and ONE jit program, with unbound slots masked out at execution time
# (executor.execute_bank's ``active`` mask).

_IDENTITY_PLAN: "list[ExecutionPlan]" = []


def identity_plan() -> ExecutionPlan:
    """The no-op padding member: no PIs, no gates, no outputs.

    Merging it into a bank contributes zero passes and zero streams; it
    exists so a template's slot count can be padded to a fixed size.  A
    process-wide singleton (held outside the LRU cache, so eviction can never
    split its identity and fork bank-template cache keys).
    """
    if not _IDENTITY_PLAN:
        _IDENTITY_PLAN.append(compile_plan(Netlist(IDENTITY_NAME)))
    return _IDENTITY_PLAN[0]


def bucket_count(n: int, min_count: int = 1) -> int:
    """Smallest power of two >= max(n, min_count) — the slot-count bucket."""
    n = max(n, min_count, 1)
    return 1 << (n - 1).bit_length()


def template_members(plans: "list[ExecutionPlan]", n_slots: int | None = None,
                     pad_counts: bool = True,
                     pad_total: bool = False) -> "tuple[ExecutionPlan, ...]":
    """Canonical padded slot layout for a request multiset.

    Distinct structures are laid out in compile-serial order, each repeated
    to its (power-of-two-padded, when ``pad_counts``) count; identity padding
    members fill the tail up to ``n_slots`` (or, with ``pad_total`` and no
    explicit ``n_slots``, up to the next power of two of the padded member
    count).  Two request sets whose padded multisets agree produce the
    *identical* tuple — the bank-template bucket key.
    """
    counts: "dict[ExecutionPlan, int]" = {}
    for p in plans:
        counts[p] = counts.get(p, 0) + 1          # plans intern: id == structure
    members: "list[ExecutionPlan]" = []
    for p in sorted(counts, key=lambda q: q.serial):
        c = counts[p]
        members.extend([p] * (bucket_count(c) if pad_counts else c))
    if n_slots is None and pad_total:
        n_slots = bucket_count(len(members))
    if n_slots is not None:
        if len(members) > n_slots:
            raise ValueError(f"template needs {len(members)} slots, "
                             f"n_slots={n_slots}")
        members.extend([identity_plan()] * (n_slots - len(members)))
    return tuple(members)


def compile_bank_template(plans: "list[ExecutionPlan]",
                          n_slots: int | None = None, pad_counts: bool = True,
                          pad_total: bool = False,
                          name: str | None = None, scope=None) -> BankPlan:
    """Compile the canonical padded bank for a request multiset (cached).

    The returned BankPlan's member list is the ``template_members`` layout;
    bind requests to the slots holding their plan and execute with
    ``executor.execute_bank(..., active=mask)``.  Padded execution is
    bit-identical per bound slot to standalone ``execute`` — unbound slots
    only ever add masked no-op work.

    ``scope`` (any hashable, default ``None``) partitions the cache: the
    multi-bank server passes the target *device*, so each device serves from
    its own template instance — one device's LRU churn cannot evict the
    templates (and the jit executables their serials anchor) another device
    is still serving from, and bucket-warmth bookkeeping keyed on
    ``BankPlan.serial`` is automatically per device.

    Each bound slot still draws from its own request's key (unbound slots
    generate nothing), so padding never perturbs results.

    Example::

        plans = [compile_plan(circuits.sc_multiply())] * 3
        tmpl = compile_bank_template(plans)    # 3 slots pad to 4
        len(tmpl.members), tmpl.members[-1].is_identity  # (4, True)
        executor.run(slot_reqs, template=tmpl, active=mask)
    """
    if not plans:
        raise ValueError("compile_bank_template: need at least one plan")
    members = template_members(plans, n_slots=n_slots, pad_counts=pad_counts,
                               pad_total=pad_total)
    return _cached_bank(members, (members, True, scope),
                        name or f"tmpl{len(members)}")


def compile_bank_members(members: "tuple[ExecutionPlan, ...]",
                         name: str | None = None, scope=None) -> BankPlan:
    """Compile a bank for an *explicit* slot layout (cached).

    ``members`` is a ready-made slot tuple — typically a ``template_members``
    layout the serving dispatcher computed once and then binds requests
    against, compiling the actual bank lazily per target device (``scope``,
    see ``compile_bank_template``).  No padding is applied: the caller owns
    the layout, and re-deriving it here could re-pad identity tails into a
    different (non-canonical) tuple.
    """
    if not members:
        raise ValueError("compile_bank_members: need at least one member")
    members = tuple(members)
    return _cached_bank(members, (members, True, scope),
                        name or f"tmpl{len(members)}")


def merged_pass_count(plans: "list[ExecutionPlan]") -> int:
    """Fused passes a bank merging exactly ``plans`` would execute.

    Mirrors ``merge_plans``'s batching rule — per level, one pass per
    distinct (op, neg) across members, combinational and sequential groups
    leveled independently — without building the merged plan.  Used by
    ``arch.evaluate_bank_plan`` to price padded-slot overhead: the padded
    bank's pass count minus the active members' merged pass count is the
    work padding added.
    """
    total = 0
    for seq in (False, True):
        by_level: "dict[int, set]" = defaultdict(set)
        for p in plans:
            if p.is_sequential != seq:
                continue
            for lvl, lev in enumerate(p.levels):
                for cop in lev:
                    by_level[lvl].add((cop.op, cop.neg))
        total += sum(len(s) for s in by_level.values())
    return total


#: Plan -> its state-cone split; weak, so a split lives as long as its plan.
_CONE_SPLITS: "weakref.WeakKeyDictionary[ExecutionPlan, ConeSplit]" = \
    weakref.WeakKeyDictionary()


def state_cone_split(plan: ExecutionPlan) -> ConeSplit:
    """``split_state_cone(plan)``, once per plan object.

    The halves are plans themselves and key jit caches downstream (the
    megakernel's), so one plan must always yield the same two.
    """
    split = _CONE_SPLITS.get(plan)
    if split is None:
        split = _CONE_SPLITS[plan] = split_state_cone(plan)
    return split
