"""Fused bit-parallel execution of compiled netlist plans.

Executes ``core.plan.ExecutionPlan``s over packed uint32 bitstream words.
Each ``CompiledOp`` — all same-type gates of one topological level — becomes
ONE bitwise pass over stacked words, the TPU analogue of the paper's
intra-subarray SIMD gate execution (a whole gate level fires in one VPU
pass, like all rows of a subarray firing in one cycle).  Two backends per
pass:

  * pure jnp bitwise ops (default): XLA fuses the whole plan into a single
    kernel under jit;
  * the Pallas packed-logic kernel (``use_pallas=True``): routes 1/2/3-input
    passes through ``packed_logic.py``'s VMEM-tiled kernel, including the
    fused 4-gate MUX path.

Sequential (stateful) netlists — the Gaines-divider class — are split at
their state cone.  The gates that read no state run once over the whole
packed streams, like a combinational plan; only the cone, the gates whose
inputs reach back to a flip-flop, runs as a ``lax.scan`` over *words* with
an inner 32-step bit loop.  The feedback wavefront never materializes the
eager time-major (BL, ...) bit tensor the interpreter builds (32x less live
memory at BL=1024, and the whole recurrence stays inside one jit).

Everything here is bit-identical to the gate-by-gate interpreter: fused ops
are boolean identities and per-gate fault injection uses the same per-gate
key assignment (see ``core/executor.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import bitstream as bs
from ..core import faults as _faults
from ..core import obs
from ..core.plan import FUSED_MUX, ExecutionPlan, state_cone_split
from .packed_logic import packed_logic

# Plan op -> packed_logic op name (ops the Pallas kernel implements).
_PALLAS_OPS = {"NOT": "not", "AND": "and", "NAND": "nand", "OR": "or",
               "NOR": "nor", "XOR": "xor", FUSED_MUX: "mux"}


def _apply_pass(op: str, ins: list[jax.Array], use_pallas: bool,
                neg: tuple[bool, ...] = (),
                interpret: bool | None = None) -> jax.Array:
    """One fused pass over stacked packed words (any leading batch shape).

    ``neg[j]`` complements input ``j`` first — the absorbed-lone-NOT form of
    ``core/plan.py``'s NOT fusion (an exact identity: complementing inside
    the pass equals materializing the NOT's output stream).  On the Pallas
    path the mask folds into the kernel itself (an in-register read), so no
    separate full-tensor complement op ever materializes; ``interpret``
    forwards to ``packed_logic`` (None: compiled on a TPU).
    """
    if use_pallas and op in _PALLAS_OPS and ins[0].ndim >= 2:
        return packed_logic(_PALLAS_OPS[op], *ins, neg=tuple(neg),
                            interpret=interpret)
    if any(neg):
        ins = [~x if nb else x for x, nb in zip(ins, neg)]
    if op == "BUFF":
        return ins[0]
    if op == FUSED_MUX:
        return bs.mux(*ins)
    return bs.GATE_FNS[op](*ins)


def run_combinational(plan: ExecutionPlan, env: dict[str, jax.Array],
                      gate_fkeys: jax.Array | None = None,
                      bitflip_rate: float = 0.0,
                      use_pallas: bool = False,
                      fault_model=None,
                      megakernel: bool = False,
                      interpret: bool | None = None) -> dict[str, jax.Array]:
    """Evaluate the plan's levels in-place over ``env`` (node -> words).

    ``gate_fkeys``: per-gate fault keys indexed by original gate id; when
    given (with ``bitflip_rate > 0`` or a non-null ``fault_model``) every
    pass output is faulted with its gate's own key — matching the
    interpreter's injection points, which requires an unfused plan
    (``compile_plan(net, fuse_mux=False)``).  ``fault_model`` generalizes
    the flat rate to the STT-MRAM taxonomy (``core/faults.py``): each gate's
    output stream occupies its own array rows, so its stuck/dead masks
    derive from that gate's key.

    ``megakernel=True`` lowers the whole plan into ONE Pallas kernel
    (``plan_megakernel``) when it can — homogeneous PI shapes and a
    liveness-annotated plan — falling back to the per-pass path otherwise
    (counted as ``megakernel.declined`` in ``obs.REGISTRY``).  Fault
    injection faults individual pass outputs, which the fused kernel never
    materializes, so the combination is rejected.

    The per-pass path releases dead intermediates as it goes: after each
    pass, every node in ``cop.free_after`` (computed by the compiler's
    liveness stage) is dropped from ``env``, bounding eager/interpret
    residency at ``plan.max_live`` streams instead of one per node.

    Every operation of the passes carries the name scope ``sc.passes``, so
    the device trace names this layer.
    """
    with jax.named_scope("sc.passes"):
        inject = gate_fkeys is not None and \
            _faults.injecting(bitflip_rate, fault_model)
        if inject and plan.fused:
            raise ValueError("per-gate fault injection requires an unfused plan")
        if megakernel:
            if inject:
                raise ValueError(
                    "megakernel execution cannot inject per-gate faults: "
                    "intermediate pass outputs never leave the kernel")
            from .plan_megakernel import combinational_megakernel
            res = combinational_megakernel(plan, env, interpret=interpret)
            if res is not None:
                env.update(res)
                return env
        for level in plan.levels:
            for cop in level:
                k = cop.n_batched
                if k == 1:
                    ins = [env[names[0]] for names in cop.inputs]
                    outs = [_apply_pass(cop.op, ins, use_pallas, cop.neg,
                                        interpret)]
                else:
                    outs = _batched_pass(cop, env, use_pallas, interpret)
                if inject:
                    outs = [_faults.apply_faults(gate_fkeys[gid], o,
                                                 bitflip_rate, fault_model)
                            for gid, o in zip(cop.gids, outs)]
                for name, o in zip(cop.outputs, outs):
                    env[name] = o
                for name in cop.free_after:
                    env.pop(name, None)
        # Re-expose nodes elided by BUFF elision / CSE: each aliases the
        # surviving node computing the identical stream, so outputs and state
        # drivers that were deduplicated away stay readable (zero extra
        # passes).
        for src, dst in plan.aliases:
            env[src] = env[dst]
        return env


def _batched_pass(cop, env: dict[str, jax.Array], use_pallas: bool,
                  interpret: bool | None = None) -> list[jax.Array]:
    """Execute one multi-gate CompiledOp, allowing heterogeneous batch shapes.

    Bank-merged plans batch gates from different member netlists into one op,
    and members may carry different batch shapes (one member serves a (8,)
    request while another serves a scalar).  Gates are grouped by input-shape
    signature; each group stacks into one fused pass, so same-shape members
    still share a single pass while differently-shaped ones keep their native
    shapes — no broadcasting, which keeps every node's stream (and therefore
    fault injection and decode) bit-identical to a per-member run.
    """
    k = cop.n_batched
    rows = [[env[n] for n in names] for names in cop.inputs]   # arity x k
    groups: dict[tuple, list[int]] = {}
    for i in range(k):
        sig = tuple(row[i].shape for row in rows)
        groups.setdefault(sig, []).append(i)

    outs: list[jax.Array | None] = [None] * k
    for idxs in groups.values():
        if len(idxs) == 1:
            i = idxs[0]
            outs[i] = _apply_pass(cop.op, [row[i] for row in rows], use_pallas,
                                  cop.neg, interpret)
            continue
        ins = [jnp.stack([row[i] for i in idxs]) for row in rows]
        stacked = _apply_pass(cop.op, ins, use_pallas, cop.neg, interpret)
        for j, i in enumerate(idxs):
            outs[i] = stacked[j]
    return outs


def run_sequential(plan: ExecutionPlan, pi_words: dict[str, jax.Array],
                   use_pallas: bool = False,
                   n_words: int | None = None,
                   batch_shape: tuple[int, ...] | None = None,
                   megakernel: bool = False,
                   interpret: bool | None = None) -> dict[str, jax.Array]:
    """Run a stateful plan: its state cone bit by bit, the rest once.

    ``pi_words``: packed streams for every non-state PI, shape (..., W).
    Returns packed output streams of the same shape.  State cells are carried
    across bits (the paper's wavefront across subarrays); bit ``t`` of the
    output is the circuit's emission at time step ``t``, with state read
    *before* update — exactly the interpreter's scan semantics.

    The plan is split at its state cone (``core.plan.state_cone_split``).
    The gates outside it read no state, so they run first as one
    ``run_combinational`` over the whole packed streams.  Only the cone runs
    inside the scan over words and its 32-step bit loop, which takes each
    boundary node (a stream PI or hoisted node the cone reads) as its own
    (W, ...) array and extracts bit ``t`` of its word.  An output outside
    the cone comes from the first run whole.  Exact: every op is bitwise,
    so bit ``t`` of a hoisted word is the bit a per-bit run gives at step
    ``t``.  Counters ``scan.hoisted_outputs``/``scan.serial_outputs`` of
    ``obs.REGISTRY`` add the batched pass outputs on each side, once per
    trace.

    Members of a bank-merged sequential plan may carry different (broadcast-
    compatible) batch shapes; the hoisted gates run at native shapes and
    their outputs broadcast to the common shape the scan runs at, and the
    caller restricts each member's outputs back to its native shape (exact:
    every op is elementwise, so restriction commutes with the recurrence).
    Plans with zero stream PIs (state-only recurrences, e.g. a NOT-feedback
    oscillator) have nothing to hoist — ``n_words`` then supplies the scan
    length that is otherwise read off the streams, and ``batch_shape`` the
    batch shape that is otherwise read off their leading dims (without it a
    batched request would silently collapse to scalar state and outputs).

    ``use_pallas``/``megakernel``/``interpret`` forward to both halves.  All
    of it carries the name scope ``sc.scan`` in the device trace; the
    hoisted run's own ``sc.passes`` scope nests under it.
    """
    with jax.named_scope("sc.scan"):
        split = state_cone_split(plan)
        obs.REGISTRY.inc("scan.hoisted_outputs", split.n_hoisted)
        obs.REGISTRY.inc("scan.serial_outputs", split.n_serial)
        names = plan.stream_pi_names()
        if names:
            common = jnp.broadcast_shapes(*(pi_words[n].shape for n in names))
        elif n_words is None:
            raise ValueError(
                f"plan {plan.name} has no stream PIs; pass n_words "
                "(= bitstream_length // 32) to size the scan")
        else:
            common = (*(batch_shape or ()), n_words)
        batch, w = tuple(common[:-1]), common[-1]

        env = {n: pi_words[n] for n in names}
        if split.hoisted is not None:
            run_combinational(split.hoisted, env, use_pallas=use_pallas,
                              megakernel=megakernel, interpret=interpret)
        words = {n: jnp.broadcast_to(env[n], common)
                 for n in split.pre_outputs}
        outs = {o: words[src] for o, src in split.taken_outputs}
        if split.serial_outputs:
            xs = tuple(jnp.moveaxis(words[b], -1, 0)           # each (W, ...)
                       for b in split.boundary)
            ys = _bit_loop(split, xs, batch, w, use_pallas, megakernel,
                           interpret)
            outs.update((o, jnp.moveaxis(y, 0, -1))
                        for o, y in zip(split.serial_outputs, ys))
        return {o: outs[o] for o in plan.outputs}


def _bit_loop(split, xs: tuple[jax.Array, ...], batch: tuple[int, ...],
              n_words: int, use_pallas: bool, megakernel: bool,
              interpret: bool | None) -> tuple[jax.Array, ...]:
    """The state cone as a scan over words with an inner 32-step bit loop.

    ``xs[k]`` holds ``split.boundary[k]``'s words, (W, ...).  Returns each
    serial output's packed words, (W, ...).
    """
    cone = split.cone
    state0 = tuple(jnp.full(batch, jnp.uint32(round(init)))
                   for init in cone.state_inits)

    def word_step(state, word):                    # word: one (...) per node
        zeros = tuple(jnp.zeros(batch, jnp.uint32)
                      for _ in split.serial_outputs)

        def bit_step(i, carry):
            state, out_words = carry
            sh = jnp.uint32(i)
            env = {n: (x >> sh) & jnp.uint32(1)
                   for n, x in zip(split.boundary, word)}
            env.update(zip(cone.state_pis, state))
            run_combinational(cone, env, use_pallas=use_pallas,
                              megakernel=megakernel, interpret=interpret)
            new_state = tuple(env[d] for d in cone.state_drivers)
            # Mask to bit 0 before packing: inverting gates (~x) carry
            # garbage in bits 1..31 of the per-bit env values.
            out_words = tuple(w | ((env[o] & jnp.uint32(1)) << sh)
                              for w, o in zip(out_words, split.serial_outputs))
            return new_state, out_words

        return jax.lax.fori_loop(0, bs.WORD_BITS, bit_step, (state, zeros))

    _, ys = jax.lax.scan(word_step, state0, xs, length=n_words)
    return ys
