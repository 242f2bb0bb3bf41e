"""Whole-plan Pallas megakernel: an ExecutionPlan as ONE fused kernel.

The per-pass path (``netlist_exec``) issues one kernel per fused pass, so
every intermediate node stream round-trips through HBM-equivalent buffers
between passes.  This module lowers an entire combinational plan (or the
combinational body of a sequential plan's scan step) into a single
``pallas_call`` gridded over lane-dense ``(sublane_tiles, lane_tiles)`` of
the streams (``common.lane_tiles``: a frame of short streams puts its
pixels on the lanes):

  * each tile's PI streams load once into a VMEM scratch *pool* sized by the
    liveness stage's ``plan.max_live`` — NOT by node count — and every
    level's bitwise passes run without the tile ever leaving VMEM;
  * per-input complement masks (``CompiledOp.neg``) fold into the in-register
    reads, and the fused MUX/XOR/AND plan-level ops execute as single
    expressions;
  * only the plan's declared outputs (and state drivers) write back.

This is the TPU analogue of the paper's intra-subarray residency: a gate
level's operands and results stay inside the array (here: VMEM) instead of
streaming in and out per gate pass.  Exact, not approximate — combinational
SC streams are word-parallel, every op is bitwise, and the scratch assignment
never recycles a slot while its node is still live (``stages.assign_liveness``
releases a pass's dying inputs only after the pass, so batched gates cannot
clobber a sibling's operand).  Off-TPU the kernel runs in interpret mode,
bit-identical to the jnp per-pass path.

A plan the kernel cannot take falls back to the per-pass path, and says so:
every decline adds to the ``megakernel.declined`` counter of
``obs.REGISTRY`` (with a ``megakernel.declined`` event on the current
trace), every lowering to ``megakernel.lowered``.  Both count traces, not
calls: a cached jit program reruns without tracing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import bitstream as bs
from ..core import obs
from ..core.plan import FUSED_MUX, ExecutionPlan
from .common import from_lane_tiles, lane_tiles, resolve_interpret

#: program instruction: (op, neg, in_slot_rows, out_slots) — all static.


def _plan_program(plan: ExecutionPlan):
    """Compile the plan's levels into a static slot program.

    Returns ``(program, slot_of)`` where ``slot_of`` maps every materialized
    node name to its scratch slot, or ``None`` when the plan carries no
    liveness assignment (pre-liveness plans have empty ``pi_slots``).
    """
    if len(plan.pi_slots) != len(plan.pis):
        return None
    slot_of = {pi.name: s for pi, s in zip(plan.pis, plan.pi_slots) if s >= 0}
    program = []
    for level in plan.levels:
        for cop in level:
            if len(cop.slots) != len(cop.outputs):
                return None
            in_rows = tuple(tuple(slot_of[nm] for nm in row)
                            for row in cop.inputs)
            neg = cop.neg if cop.neg else (False,) * len(cop.inputs)
            program.append((cop.op, neg, in_rows, cop.slots))
            for nm, s in zip(cop.outputs, cop.slots):
                slot_of[nm] = s
    return program, slot_of


def _apply_op(op: str, args: list[jax.Array]) -> jax.Array:
    if op == FUSED_MUX:
        return bs.mux(*args)
    return bs.GATE_FNS[op](*args)


def _kernel(program, pi_slots, out_slots, pi_ref, out_ref, scratch):
    # Load this tile's PI streams into their scratch slots.
    for k, s in enumerate(pi_slots):
        scratch[s] = pi_ref[k]
    # Every level's passes, gate by gate — static Python loops, fully
    # unrolled at trace time; slots recycle per the liveness assignment.
    for op, neg, in_rows, slots in program:
        for g, out_slot in enumerate(slots):
            args = []
            for row, nb in zip(in_rows, neg):
                v = scratch[row[g]]
                args.append(~v if nb else v)
            scratch[out_slot] = _apply_op(op, args)
    # Only declared outputs leave VMEM.
    for k, s in enumerate(out_slots):
        out_ref[k] = scratch[s]


def _decline(plan: ExecutionPlan, reason: str) -> None:
    obs.REGISTRY.inc("megakernel.declined")
    obs.event("megakernel.declined", plan=plan.name, reason=reason)


def combinational_megakernel(plan: ExecutionPlan,
                             env: dict[str, jax.Array], *,
                             block_sub: int = 8, block_lanes: int = 128,
                             interpret: bool | None = None,
                             ) -> dict[str, jax.Array] | None:
    """Run a combinational plan as one fused Pallas kernel.

    ``env`` maps every stream/state PI name to its packed words (any common
    shape; the kernel views it as ``common.lane_tiles``).  Returns the
    plan's observable streams — outputs and state drivers, aliases resolved
    — or ``None`` when the plan cannot lower (no liveness info, or
    heterogeneous PI shapes, as in a merged bank serving mixed batch
    shapes); the caller then falls back to the per-pass path, and the
    decline is counted (module docstring).
    """
    prog = _plan_program(plan)
    if prog is None:
        _decline(plan, "no liveness assignment")
        return None
    program, slot_of = prog

    alias = dict(plan.aliases)
    out_names: list[str] = []
    for nm in (*plan.outputs, *plan.state_drivers):
        r = alias.get(nm, nm)
        if r not in out_names:
            out_names.append(r)
    if not out_names:
        return {}

    pi_names = [pi.name for pi, s in zip(plan.pis, plan.pi_slots) if s >= 0]
    shapes = {env[nm].shape for nm in pi_names}
    if len(shapes) > 1:
        _decline(plan, "heterogeneous PI shapes")
        return None
    if plan.max_live == 0 or not pi_names:
        _decline(plan, "no stream PIs")
        return None
    (shape,) = shapes

    tiles = [lane_tiles(env[nm]) for nm in pi_names]
    tile_shape = tiles[0].shape
    stacked = jnp.stack([t.reshape(-1, tile_shape[-1]) for t in tiles])
    out = _megakernel_call(
        plan, tuple(slot_of[nm] for nm in pi_names),
        tuple(slot_of[nm] for nm in out_names), len(out_names),
        stacked, block_sub, block_lanes, interpret)
    obs.REGISTRY.inc("megakernel.lowered")
    return {nm: from_lane_tiles(
                out[out_names.index(alias.get(nm, nm))].reshape(tile_shape),
                shape)
            for nm in (*plan.outputs, *plan.state_drivers)}


@functools.partial(jax.jit, static_argnames=(
    "plan", "pi_slots", "out_slots", "n_out", "block_sub", "block_lanes",
    "interpret"))
def _megakernel_call(plan: ExecutionPlan, pi_slots, out_slots, n_out: int,
                     stacked: jax.Array, block_sub: int, block_lanes: int,
                     interpret: bool | None) -> jax.Array:
    """The jitted pallas_call: (P, sub, lanes) PI stack -> (O, sub, lanes).

    The plan is a static arg (interned, identity-hashed), so the slot program
    rebuilds only per plan per shape — one trace, one kernel.  A block holds
    ``P + max_live + O`` tiles of ``block_sub x block_lanes`` words in VMEM.
    """
    program, _ = _plan_program(plan)
    p, sub, lanes = stacked.shape
    sub_blk, lane_blk = min(block_sub, sub), min(block_lanes, lanes)
    grid = (pl.cdiv(sub, sub_blk), pl.cdiv(lanes, lane_blk))
    kernel = functools.partial(_kernel, program, pi_slots, out_slots)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((p, sub_blk, lane_blk), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((n_out, sub_blk, lane_blk),
                               lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_out, sub, lanes), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((plan.max_live, sub_blk, lane_blk),
                                   jnp.uint32)],
        interpret=resolve_interpret(interpret),
        name="plan_megakernel",
    )(stacked)
