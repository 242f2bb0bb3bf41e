"""Execution dispatch: the jit boundary, value packing, and backends.

Middle layer of the executor stack (``streams`` <- ``dispatch`` <-
``exec_api`` <- the ``executor`` facade).  Owns everything that crosses the
host/XLA boundary:

  * the jitted whole-plan / whole-bank programs (``_execute_compiled``,
    ``_execute_bank``) and their static-argument discipline;
  * host-side argument normalization (keys, batch shapes, active masks) and
    the slot-packed value layout ``_pack_values_seq`` — host scalars collapse
    to one f32 vector per slot and host arrays to one stacked leaf per
    (slot, shape) group, so the jit boundary flattens a handful of leaves
    per slot instead of one per PI;
  * the gate-by-gate reference interpreter (``_execute_reference``), the
    oracle the compiled path is tested against.

Fault keying mirrors the reference interpreter exactly (whatever the
``key_mode``): one fkey per sorted PI stream, then one per gate id
(combinational) / per sorted output (sequential).
"""
from __future__ import annotations

import contextlib
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import bitstream as bs
from . import faults as _faults
from . import obs
from .faults import FaultModel
from .gates import Netlist
from .plan import BankPlan, ExecutionPlan, compile_bank_plan, compile_plan, member_prefix
from .streams import (_BACKENDS, _KEY_MODES, DEFAULT_BACKEND, DEFAULT_KEY_MODE,
                      _gen_bank_streams, _gen_pi_streams)

# ------------------------------ compiled backend ----------------------------------


@partial(jax.jit, static_argnames=("plan", "bitstream_length", "bitflip_rate",
                                   "use_pallas", "decode", "key_mode",
                                   "batch_shape", "fault_model", "word_chunk",
                                   "megakernel", "interpret"))
def _execute_compiled(plan: ExecutionPlan, values: dict[str, jax.Array],
                      key: jax.Array, flip_key, bitstream_length: int,
                      bitflip_rate: float, use_pallas: bool,
                      decode: bool = False,
                      key_mode: str = DEFAULT_KEY_MODE,
                      batch_shape: tuple[int, ...] | None = None,
                      fault_model: FaultModel | None = None,
                      word_chunk: int | None = None,
                      megakernel: bool = False,
                      interpret: bool | None = None) -> dict[str, jax.Array]:
    """Whole-netlist execution as one XLA program.

    Mirrors the reference interpreter's key discipline exactly (whatever the
    ``key_mode``): one fkey per sorted PI stream, then one per gate id
    (combinational) / per sorted output (sequential).  ``decode=True`` folds
    the StoB popcount decode into the same program (used by execute_value),
    leaving one dispatch per call.  In batched key mode the PI streams come
    from ONE fused SNG pass over the plan's stream table — generation, logic,
    fault injection and decode are all one XLA program either way.

    ``fault_model`` (static, pre-normalized) generalizes ``bitflip_rate``:
    its transient component consumes each injection point's raw fault key —
    the same split, the same key assignment — and its persistent/static
    masks stack on top (``core/faults.py``), so a transient-only model is
    bit-identical to the legacy rate path.  Static-only models (dead
    columns, explicit cell maps) need no ``flip_key``; a placeholder key
    feeds the (unconsumed) splits.

    ``word_chunk`` streams a combinational run ``word_chunk`` words at a
    time via ``lax.scan`` instead of materializing full-length node streams:
    peak live words drop from ``plan.naive_live * W`` to roughly
    ``plan.max_live * word_chunk``.  In batched key mode each chunk's PI
    words are *regenerated* in place (the counter-based SNG is
    word-addressable — see ``bs.generate_batch_seeded``); legacy mode
    generates once and slices, so only intermediate streams are bounded.
    Exact either way: chunks of an i.i.d. bitstream are independent, every
    op is word-local, and reassembly is a pure transpose.
    ``megakernel`` selects the whole-plan Pallas kernel for the logic passes
    (``kernels/plan_megakernel``); ``interpret`` reaches every Pallas kernel
    of the program (None: compiled on a TPU, see
    ``kernels.common.resolve_interpret``).
    """
    from ..kernels import netlist_exec

    inject = _faults.injecting(bitflip_rate, fault_model)
    if word_chunk is not None:
        if plan.is_sequential:
            raise ValueError(
                "word_chunk streams combinational plans only: a sequential "
                "plan's state recurrence already scans over words "
                "(kernels/netlist_exec.run_sequential) and cannot be "
                "re-chunked; drop word_chunk for this netlist")
        if inject:
            raise ValueError(
                "word_chunk cannot combine with fault injection: "
                "stuck/dead masks index absolute stream positions")
        w = bs.n_words(bitstream_length)
        if word_chunk <= 0 or w % word_chunk != 0:
            raise ValueError(
                f"word_chunk={word_chunk} must be positive and divide the "
                f"stream length in words ({w} for BL={bitstream_length})")
        if word_chunk != w:
            return _execute_chunked(plan, values, key, bitstream_length,
                                    use_pallas, decode, key_mode, batch_shape,
                                    word_chunk, megakernel, interpret)

    streams = _gen_pi_streams(plan.pis, values, key, bitstream_length,
                              key_mode=key_mode, batch_shape=batch_shape,
                              use_pallas=use_pallas, table=plan.stream_table,
                              interpret=interpret)

    gate_fkeys = None
    if inject:
        fk = flip_key if flip_key is not None else jax.random.key(0)
        fkeys = jax.random.split(fk, len(streams) + plan.n_gates)
        for i, name in enumerate(sorted(streams)):
            streams[name] = _faults.apply_faults(fkeys[i], streams[name],
                                                 bitflip_rate, fault_model)
        gate_fkeys = fkeys[len(streams):]

    if not plan.is_sequential:
        env = dict(streams)
        netlist_exec.run_combinational(plan, env, gate_fkeys=gate_fkeys,
                                       bitflip_rate=bitflip_rate,
                                       fault_model=fault_model,
                                       use_pallas=use_pallas,
                                       megakernel=megakernel,
                                       interpret=interpret)
        packed_outs = {o: env[o] for o in plan.outputs}
    else:
        packed_outs = netlist_exec.run_sequential(
            plan, streams, use_pallas=use_pallas,
            n_words=bs.n_words(bitstream_length),
            batch_shape=batch_shape,
            megakernel=megakernel, interpret=interpret)
        if gate_fkeys is not None:
            for i, o in enumerate(sorted(packed_outs)):
                packed_outs[o] = _faults.apply_faults(gate_fkeys[i],
                                                      packed_outs[o],
                                                      bitflip_rate, fault_model)
    if decode:
        return {o: bs.to_value(w, bitstream_length)
                for o, w in packed_outs.items()}
    return packed_outs


def _execute_chunked(plan: ExecutionPlan, values, key, bitstream_length: int,
                     use_pallas: bool, decode: bool, key_mode: str,
                     batch_shape, word_chunk: int, megakernel: bool,
                     interpret: bool | None) -> dict[str, jax.Array]:
    """Word-tiled streaming execution of a combinational plan.

    One ``lax.scan`` over ``W / word_chunk`` chunks; each step holds at most
    ``plan.max_live`` streams of ``word_chunk`` words.  Batched key mode
    regenerates each chunk's PI words by absolute position
    (``word_window``); legacy threefry streams are not word-addressable, so
    that mode pre-generates once and the scan body slices (the live-words
    bound then covers intermediates only).  Chunk outputs stack on a leading
    axis and reassemble by a transpose — bit-identical to the one-shot run.
    """
    from ..kernels import netlist_exec

    w = bs.n_words(bitstream_length)
    n_chunks = w // word_chunk
    full = None
    if key_mode != "batched":
        full = _gen_pi_streams(plan.pis, values, key, bitstream_length,
                               key_mode=key_mode, batch_shape=batch_shape,
                               use_pallas=use_pallas, table=plan.stream_table,
                               interpret=interpret)

    def body(carry, ci):
        if full is None:
            streams = _gen_pi_streams(
                plan.pis, values, key, bitstream_length, key_mode=key_mode,
                batch_shape=batch_shape, use_pallas=use_pallas,
                table=plan.stream_table,
                word_window=(ci * jnp.uint32(word_chunk), word_chunk),
                interpret=interpret)
        else:
            streams = {nm: jax.lax.dynamic_slice_in_dim(
                           v, ci * jnp.uint32(word_chunk), word_chunk, axis=-1)
                       for nm, v in full.items()}
        env = dict(streams)
        netlist_exec.run_combinational(plan, env, use_pallas=use_pallas,
                                       megakernel=megakernel,
                                       interpret=interpret)
        return carry, tuple(env[o] for o in plan.outputs)

    _, ys = jax.lax.scan(body, 0, jnp.arange(n_chunks, dtype=jnp.uint32))
    packed_outs = {}
    for o, y in zip(plan.outputs, ys):      # y: (n_chunks, *batch, word_chunk)
        y = jnp.moveaxis(y, 0, -2)
        packed_outs[o] = y.reshape(y.shape[:-2] + (w,))
    if decode:
        return {o: bs.to_value(v, bitstream_length)
                for o, v in packed_outs.items()}
    return packed_outs


def _binary_env(pis, operand_bits: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """PI env for a binary netlist: supplied operands + const-PI fills."""
    env: dict[str, jax.Array] = {}
    shape = next(iter(operand_bits.values())).shape
    for pi in pis:
        if pi.name in operand_bits:
            env[pi.name] = operand_bits[pi.name]
        elif pi.const_value is not None:
            c = float(pi.const_value)
            if c == 0.0:
                fill = jnp.uint32(0)
            elif c == 1.0:
                fill = jnp.uint32(0xFFFFFFFF)
            else:
                # A binary constant cell holds one bit; flooring 0 < c < 1 to
                # an all-zeros word would silently miscompute.
                raise ValueError(
                    f"binary PI {pi.name}: const_value must be 0.0 or 1.0, "
                    f"got {pi.const_value}")
            env[pi.name] = jnp.full(shape, fill)
        else:
            raise KeyError(f"missing binary operand {pi.name}")
    return env


@partial(jax.jit, static_argnames=("plan", "use_pallas"))
def _execute_binary_compiled(plan: ExecutionPlan,
                             operand_bits: dict[str, jax.Array],
                             use_pallas: bool) -> dict[str, jax.Array]:
    from ..kernels import netlist_exec

    env = _binary_env(plan.pis, operand_bits)
    netlist_exec.run_combinational(plan, env, use_pallas=use_pallas)
    return {o: env[o] for o in plan.outputs}


def _plan_for(net: Netlist, bitflip_rate: float,
              fault_model: FaultModel | None = None) -> ExecutionPlan:
    # Per-gate fault injection must observe the 4-gate MUX intermediates, so
    # the fused plan is only valid for clean combinational runs; sequential
    # runs inject at PI/output streams only (like the reference) and may fuse.
    fuse = not _faults.injecting(bitflip_rate, fault_model) \
        or net.is_sequential
    return compile_plan(net, fuse_mux=fuse)


def _check_fault_args(bitflip_rate: float, fault_model, flip_key,
                      what: str = "flip_key") -> "FaultModel | None":
    """Normalize/validate the fault arguments shared by every entry point.

    Returns the normalized model (null models collapse to ``None`` so the
    clean path — and its jit cache entry — is taken).  ``bitflip_rate`` and
    ``fault_model`` are mutually exclusive: the model's ``flip_rate`` *is*
    the transient rate, and letting both stack would silently double-inject.
    """
    fault_model = _faults.normalize_fault_model(fault_model)
    if fault_model is not None and bitflip_rate > 0.0:
        raise ValueError(
            "pass bitflip_rate or fault_model, not both "
            "(FaultModel(flip_rate=...) subsumes bitflip_rate)")
    if bitflip_rate > 0.0 and flip_key is None:
        raise ValueError(f"bitflip_rate > 0 requires {what}")
    if fault_model is not None and fault_model.needs_keys and flip_key is None:
        raise ValueError(
            f"fault_model with random components requires {what}")
    return fault_model


def _check_modes(backend: str | None, key_mode: str | None) -> tuple[str, str]:
    backend = backend or DEFAULT_BACKEND
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {_BACKENDS}")
    key_mode = key_mode or DEFAULT_KEY_MODE
    if key_mode not in _KEY_MODES:
        raise ValueError(f"unknown key_mode {key_mode!r}; "
                         f"expected one of {_KEY_MODES}")
    return backend, key_mode


def _dispatch(net: Netlist, values, key, bitstream_length: int,
              bitflip_rate: float, flip_key, backend: str | None,
              decode: bool, key_mode: str | None = None,
              batch_shape: tuple[int, ...] | None = None,
              fault_model: FaultModel | None = None,
              word_chunk: int | None = None,
              interpret: bool | None = None) -> dict[str, jax.Array]:
    backend, key_mode = _check_modes(backend, key_mode)
    if batch_shape is not None:
        batch_shape = tuple(batch_shape)   # hashable for the jit static arg
    fault_model = _check_fault_args(bitflip_rate, fault_model, flip_key)
    if backend == "reference":
        if word_chunk is not None:
            raise ValueError("word_chunk requires a compiled backend; the "
                             "reference interpreter always materializes "
                             "full streams")
        outs = _execute_reference(net, values, key, bitstream_length,
                                  bitflip_rate, flip_key, key_mode=key_mode,
                                  batch_shape=batch_shape,
                                  fault_model=fault_model)
        if decode:
            outs = {k: bs.to_value(v, bitstream_length) for k, v in outs.items()}
        return outs
    plan = _plan_for(net, bitflip_rate, fault_model)
    values = {k: jnp.asarray(v, jnp.float32) for k, v in values.items()}
    with obs.span("exec.dispatch", plan=plan.name,
                  bitstream_length=bitstream_length):
        return _execute_compiled(plan, values, key, flip_key, bitstream_length,
                                 float(bitflip_rate),
                                 backend == "compiled_pallas", decode=decode,
                                 key_mode=key_mode, batch_shape=batch_shape,
                                 fault_model=fault_model,
                                 word_chunk=word_chunk,
                                 megakernel=backend == "compiled_megakernel",
                                 interpret=interpret)


def _dispatch_binary(net: Netlist, operand_bits: dict[str, jax.Array],
                     backend: str | None) -> dict[str, jax.Array]:
    backend = backend or DEFAULT_BACKEND
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {_BACKENDS}")
    if backend == "reference":
        env = _binary_env(net.pis, operand_bits)
        for g in net.gates:
            env[g.output] = bs.GATE_FNS[g.gtype](*[env[i] for i in g.inputs])
        return {o: env[o] for o in net.outputs}
    plan = compile_plan(net, fuse_mux=True)
    return _execute_binary_compiled(plan, dict(operand_bits),
                                    backend == "compiled_pallas")


# ----------------------------- bank-level execution -------------------------------

def _restrict(x: jax.Array, batch: tuple[int, ...]) -> jax.Array:
    """Undo a broadcast: restrict ``x`` of shape (*common, W) to (*batch, W).

    Exact, not approximate: a merged member's nodes only ever combine
    elementwise with that member's own (broadcast) streams, so the restricted
    entries equal the member's native computation bit for bit.
    """
    want = len(batch) + 1
    if x.ndim == want and x.shape[:-1] == batch:
        return x
    x = x[(0,) * (x.ndim - want)]
    for ax, d in enumerate(batch):
        if d == 1 and x.shape[ax] != 1:
            x = jax.lax.slice_in_dim(x, 0, 1, axis=ax)
    return x


@partial(jax.jit, static_argnames=("bank", "bitstream_length", "key_mode",
                                   "use_pallas", "batch_shapes", "active"))
def _generate_bank_streams_jit(bank: BankPlan, values_seq, keys,
                               bitstream_length: int, key_mode: str,
                               use_pallas: bool, batch_shapes, active=None):
    return _gen_bank_streams(bank, values_seq, keys, bitstream_length,
                             key_mode, use_pallas, batch_shapes, active=active)


def generate_bank_streams(bank: BankPlan, values_seq, keys,
                          bitstream_length: int,
                          key_mode: str = DEFAULT_KEY_MODE,
                          use_pallas: bool = False, batch_shapes=None,
                          active=None):
    """Generate (only) every member's PI streams — no logic passes.

    The stream-generation phase of ``_execute_bank`` as its own jitted entry
    point, used by the benchmarks to split bank wall-clock into gen vs pass
    time.  Accepts the same calling convention as ``execute_many`` (``keys``
    may be one key, split N ways; ``batch_shapes`` entries may be any
    sequence; ``active`` masks padded template slots down to zero-word
    fills).  Returns one ``{pi_name: packed words}`` dict per member.
    """
    values_seq = tuple(values_seq)
    if len(values_seq) != bank.n_members:
        raise ValueError(f"values: got {len(values_seq)} for "
                         f"{bank.n_members} members")
    keys = _normalize_keys(keys, bank.n_members)
    batch_shapes = _normalize_batch_shapes(batch_shapes, bank.n_members,
                                           "members")
    active = _normalize_active(active, bank.n_members)
    with obs.span("exec.stream_gen", bank=bank.name,
                  bitstream_length=bitstream_length):
        return _generate_bank_streams_jit(bank, values_seq, keys,
                                          bitstream_length, key_mode,
                                          use_pallas, batch_shapes, active)


def _unpack_values_seq(values_seq, scalar_names):
    """Trace-time inverse of ``_pack_values_seq``: rebuild per-slot dicts.

    The unpack slices are free after fusion, and the jit boundary sees a
    handful of leaves per slot instead of one per PI.
    """
    packed_seq, grouped_seq, rest_seq = values_seq
    out = []
    for i, (snames, gspecs) in enumerate(scalar_names):
        vals = {nm: packed_seq[i][j] for j, nm in enumerate(snames)}
        for (_, gnames), arr in zip(gspecs, grouped_seq[i]):
            for j, nm in enumerate(gnames):
                vals[nm] = arr[j]
        vals.update(rest_seq[i])
        out.append(vals)
    return tuple(out)


def _execute_bank_impl(bank: BankPlan, values_seq, keys, flip_keys,
                       bitstream_length: int, bitflip_rate: float,
                       use_pallas: bool, decode: bool,
                       key_mode: str = DEFAULT_KEY_MODE, batch_shapes=None,
                       active=None, scalar_names=None,
                       fault_model: FaultModel | None = None,
                       megakernel: bool = False,
                       interpret: bool | None = None):
    """Whole-bank execution of N member netlists as one XLA program.

    Stream generation and fault keying stay *per member*: member ``i``'s
    streams are drawn from ``keys[i]`` / ``flip_keys[i]`` exactly as a
    standalone ``execute`` call (same ``key_mode``) would draw them, so a
    merged run is bit-identical to a loop of per-member runs.  The logic
    merges — all combinational members execute through one merged plan
    (cross-member type-batched levels), all sequential members through one
    merged scan — and in batched key mode the stream generation merges too
    (one fused SNG pass per distinct member batch shape).

    ``active`` (static; None = all) is the padded-template slot mask: an
    inactive slot generates no real streams (zero-word fills), skips fault
    injection on its streams, and returns ``None`` instead of outputs.  Its
    *gate fault-key block* is still allocated when injecting — the merged
    plan's flat gid offsets cover every member — so active slots see exactly
    the keys a standalone run would.
    """
    from ..kernels import netlist_exec

    if scalar_names is not None:
        # Packed-slot layout (see _pack_values_seq): slot i's host-scalar PI
        # values arrive as one f32 vector and its host arrays as one stacked
        # leaf per shape group; rebuild the per-name dicts at trace time.
        values_seq = _unpack_values_seq(values_seq, scalar_names)

    comb_env: dict[str, jax.Array] = {}
    seq_words: dict[str, jax.Array] = {}
    comb_gate_fkeys: list[jax.Array] = []
    seq_out_fkeys: dict[int, jax.Array | None] = {}
    native_batch: dict[int, tuple[int, ...]] = {}
    member_streams = _gen_bank_streams(bank, values_seq, keys,
                                       bitstream_length, key_mode, use_pallas,
                                       batch_shapes, active=active,
                                       interpret=interpret)
    inject = _faults.injecting(bitflip_rate, fault_model)
    for i, plan in enumerate(bank.members):
        pre = member_prefix(i)
        streams = member_streams[i]
        masked = active is not None and not active[i]
        tail = None
        if inject and len(streams) + plan.n_gates > 0:
            fkeys = jax.random.split(flip_keys[i], len(streams) + plan.n_gates)
            if not masked:
                for j, nm in enumerate(sorted(streams)):
                    streams[nm] = _faults.apply_faults(fkeys[j], streams[nm],
                                                       bitflip_rate,
                                                       fault_model)
            tail = fkeys[len(streams):]
        native_batch[i] = (next(iter(streams.values())).shape[:-1]
                           if streams else ())
        target = seq_words if plan.is_sequential else comb_env
        for nm, v in streams.items():
            target[pre + nm] = v
        if plan.is_sequential:
            seq_out_fkeys[i] = tail
        elif tail is not None:
            # Flat per-gate key blocks in merge (= ascending member) order:
            # the merged plan's gids are offset to index this concatenation.
            comb_gate_fkeys.append(tail)

    outs: list = [None] * bank.n_members
    if bank.comb is not None:
        gf = jnp.concatenate(comb_gate_fkeys) if comb_gate_fkeys else None
        netlist_exec.run_combinational(bank.comb, comb_env, gate_fkeys=gf,
                                       bitflip_rate=bitflip_rate,
                                       fault_model=fault_model,
                                       use_pallas=use_pallas,
                                       megakernel=megakernel,
                                       interpret=interpret)
        for i in bank.comb_members:
            if active is not None and not active[i]:
                continue
            pre = member_prefix(i)
            outs[i] = {o: comb_env[pre + o] for o in bank.members[i].outputs}
    if bank.seq is not None:
        packed = netlist_exec.run_sequential(
            bank.seq, seq_words, use_pallas=use_pallas,
            n_words=bs.n_words(bitstream_length),
            megakernel=megakernel, interpret=interpret)
        for i in bank.seq_members:
            if active is not None and not active[i]:
                continue
            pre = member_prefix(i)
            m = {o: _restrict(packed[pre + o], native_batch[i])
                 for o in bank.members[i].outputs}
            if inject:
                tail = seq_out_fkeys[i]
                for j, o in enumerate(sorted(m)):
                    m[o] = _faults.apply_faults(tail[j], m[o], bitflip_rate,
                                                fault_model)
            outs[i] = m
    if decode:
        outs = [m if m is None else
                {o: bs.to_value(w, bitstream_length) for o, w in m.items()}
                for m in outs]
    return tuple(outs)


_BANK_STATIC = ("bank", "bitstream_length", "bitflip_rate", "use_pallas",
                "decode", "key_mode", "batch_shapes", "active",
                "scalar_names", "fault_model", "megakernel", "interpret")
_execute_bank = partial(jax.jit, static_argnames=_BANK_STATIC)(
    _execute_bank_impl)
#: Donating variant (its own jit cache): XLA reuses the stacked key rows'
#: buffers (argnums 2/3).  Only safe when the caller owns those arrays and
#: never reads them after the call — the serve engine's per-batch stacks.
#: Slot *values* are never donated: they may alias caller-held request
#: arrays.
_execute_bank_donating = partial(jax.jit, static_argnames=_BANK_STATIC,
                                 donate_argnums=(2, 3))(_execute_bank_impl)


#: type -> "is a jax.Array subclass" memo: ``isinstance(v, jax.Array)`` goes
#: through ABC registration machinery, which shows up at bank-dispatch rates
#: (thousands of value leaves per batch).
_IS_JAX_ARRAY: dict = {}


def _is_jax_array(v) -> bool:
    t = type(v)
    is_jax = _IS_JAX_ARRAY.get(t)
    if is_jax is None:
        is_jax = _IS_JAX_ARRAY.setdefault(t, isinstance(v, jax.Array))
    return is_jax


def _as_f32(v) -> jax.Array:
    """asarray(v, float32), skipping the (surprisingly costly) conversion
    machinery on the serving hot path when the caller already holds f32."""
    if _is_jax_array(v) and v.dtype == jnp.float32:
        return v
    return jnp.asarray(v, jnp.float32)


def _is_host_scalar(v) -> bool:
    return not _is_jax_array(v) and np.ndim(v) == 0


def _pack_values_seq(values_seq):
    """Slot-packed jit layout for bank dispatch:
    ``(packed, grouped, rest), names``.

    Each slot's *host scalar* PI values (python/numpy scalars — the serving
    admission format) collapse into one f32 vector, and its *host array*
    (batched, non-jax) values stack into one f32 leaf per distinct shape —
    so the jit boundary flattens/transfers a handful of leaves per slot
    instead of one per PI (a LIT slot alone carries 81 scalars; a batched OL
    slot a (16, 6) array per column group).  ``names[i]`` records slot i's
    layout — ``(scalar_names, ((shape, group_names), ...))``, both in sorted
    order — as a static jit argument; ``_unpack_values_seq`` rebuilds the
    dicts at trace time.  jax-array leaves are NOT packed — pulling them
    back to host would force a device sync — and flow through ``rest``
    unchanged.
    """
    packed, grouped, rest, names = [], [], [], []
    for vals in values_seq:
        scalars = []
        by_shape: dict[tuple[int, ...], list[str]] = {}
        jax_rest = {}
        for k, v in vals.items():
            if _is_jax_array(v):
                jax_rest[k] = _as_f32(v)
            elif np.ndim(v) == 0:
                scalars.append(k)
            else:
                by_shape.setdefault(np.shape(v), []).append(k)
        scalars.sort()
        gspecs, garrs = [], []
        for shape in sorted(by_shape):
            ks = sorted(by_shape[shape])
            gspecs.append((shape, tuple(ks)))
            garrs.append(np.stack([np.asarray(vals[k], np.float32)
                                   for k in ks]))
        packed.append(np.asarray([vals[k] for k in scalars], np.float32))
        grouped.append(tuple(garrs))
        rest.append(jax_rest)
        names.append((tuple(scalars), tuple(gspecs)))
    return (tuple(packed), tuple(grouped), tuple(rest)), tuple(names)


def _normalize_batch_shapes(batch_shapes, n: int, what: str = "netlists"):
    """Coerce per-member batch shapes to a hashable tuple-of-tuples (jit
    static arg) and validate the member count; None passes through."""
    if batch_shapes is None:
        return None
    batch_shapes = tuple(tuple(b) if b is not None else None
                         for b in batch_shapes)
    if len(batch_shapes) != n:
        raise ValueError(
            f"batch_shapes: got {len(batch_shapes)} for {n} {what}")
    return batch_shapes


def _normalize_active(active, n: int):
    """Coerce a slot-active mask to a hashable bool tuple (jit static arg).

    ``None`` and all-True both normalize to ``None`` — a fully-bound bank
    must share its jit trace with the mask-free ``execute_many`` path.
    """
    if active is None:
        return None
    active = tuple(bool(a) for a in active)
    if len(active) != n:
        raise ValueError(f"active: got {len(active)} for {n} slots")
    return None if all(active) else active


def _normalize_keys(keys, n: int, what: str = "keys") -> jax.Array:
    """Accept one key (split n ways), a key array, or a sequence of keys.

    Returns a stacked (n,) key array — members index it *inside* the jitted
    program, so the per-member key slicing costs no host dispatches.
    """
    if isinstance(keys, (list, tuple)):
        keys = jnp.stack(keys)
    elif jnp.ndim(keys) == 0:
        keys = jax.random.split(keys, n)
    if keys.shape[0] != n:
        raise ValueError(f"{what}: got {keys.shape[0]} for {n} netlists")
    return keys


def _fault_flip_keys(flip_keys, n: int, bitflip_rate: float,
                     fault_model: "FaultModel | None"):
    """Normalize per-member fault keys for a bank dispatch.

    When injecting, the bank impl splits a key per member unconditionally;
    a static-only model (no random components) may run keyless, so a
    deterministic placeholder fills in — its splits are never consumed.
    """
    if not _faults.injecting(bitflip_rate, fault_model):
        return None
    if flip_keys is None:
        return _normalize_keys(jax.random.key(0), n, "flip_keys")
    return _normalize_keys(flip_keys, n, "flip_keys")


def _dispatch_many(nets, values_seq, keys, bitstream_length: int,
                   bitflip_rate: float, flip_keys, backend: str | None,
                   decode: bool, key_mode: str | None = None,
                   batch_shapes=None,
                   fault_model: FaultModel | None = None,
                   interpret: bool | None = None) -> list:
    backend, key_mode = _check_modes(backend, key_mode)
    n = len(nets)
    if n == 0:
        raise ValueError("execute_many: need at least one netlist")
    if len(values_seq) != n:
        raise ValueError(f"values: got {len(values_seq)} for {n} netlists")
    batch_shapes = _normalize_batch_shapes(batch_shapes, n)
    keys = _normalize_keys(keys, n)
    fault_model = _check_fault_args(bitflip_rate, fault_model, flip_keys,
                                    "flip_keys")
    flip_keys = _fault_flip_keys(flip_keys, n, bitflip_rate, fault_model)
    if backend == "reference":
        return [_dispatch(net, dict(vals), keys[i], bitstream_length,
                          bitflip_rate,
                          flip_keys[i] if flip_keys is not None else None,
                          backend, decode, key_mode=key_mode,
                          batch_shape=batch_shapes[i] if batch_shapes else None,
                          fault_model=fault_model)
                for i, (net, vals) in enumerate(zip(nets, values_seq))]
    bank = compile_bank_plan(
        list(nets),
        fuse_mux=not _faults.injecting(bitflip_rate, fault_model))
    values_seq, scalar_names = _pack_values_seq(values_seq)
    outs = _execute_bank(bank, values_seq, keys, flip_keys, bitstream_length,
                         float(bitflip_rate), backend == "compiled_pallas",
                         decode, key_mode=key_mode, batch_shapes=batch_shapes,
                         scalar_names=scalar_names, fault_model=fault_model,
                         megakernel=backend == "compiled_megakernel",
                         interpret=interpret)
    return list(outs)


def execute_bank(bank: BankPlan, values_seq, keys, bitstream_length: int,
                 *, active=None, bitflip_rate: float = 0.0, flip_keys=None,
                 backend: str | None = None, key_mode: str | None = None,
                 batch_shapes=None, decode: bool = False,
                 device=None, donate: bool = False,
                 fault_model: FaultModel | None = None,
                 interpret: bool | None = None) -> list:
    """Execute a prebuilt (possibly padded) BankPlan slot-wise.

    The serving-engine entry point (``repro.serve.sc_engine``): ``bank`` is
    typically a canonical template from ``plan.compile_bank_template`` whose
    slots outnumber the bound requests.  ``values_seq[i]`` / ``keys[i]`` /
    ``batch_shapes[i]`` / ``flip_keys[i]`` feed slot ``i``; ``active[i] =
    False`` masks slot ``i`` out — no streams are generated for it (zero-word
    fills keep the merged passes well-formed), and its entry in the returned
    list is ``None``.  Unbound slots' ``values_seq`` entries should be empty
    dicts; their key rows are placeholders (any same-dtype key).

    Every *bound* slot's outputs are bit-identical to a standalone
    ``execute`` of that member with the same key, ``key_mode`` and flip key —
    padding never perturbs active streams.  ``decode=True`` fuses the StoB
    decode into the program (the ``execute_value_many`` analogue).  Bank
    plans only execute on the compiled backends.

    ``device`` (a ``jax.Device``) commits the stacked key rows there before
    dispatch; jit places the whole bank execution with its committed
    argument, so the program runs on that device and the outputs live there
    — the multi-bank server's sharded placement.  Only the key arrays are
    committed (one buffer each): committing the per-slot values pytree
    leaf-by-leaf costs more host time than the dispatch itself, while
    uncommitted values follow the keys in one transfer.  Values already
    committed to a *different* device raise jax's colocation error — pass
    host/uncommitted values when sharding.  ``donate=True`` lets XLA consume
    the stacked key-row buffers (never the slot values, which may alias
    caller arrays); only pass it when the key rows are call-owned scratch,
    like the serve engine's per-batch stacks.
    """
    fn, args, kw = _bank_call(bank, values_seq, keys, bitstream_length,
                              active, bitflip_rate, flip_keys, backend,
                              key_mode, batch_shapes, decode, donate,
                              fault_model, interpret)
    if device is not None:
        with obs.span("exec.device_transfer", device=str(device)):
            args = _with_keys(args, lambda k: jax.device_put(k, device))
    # NOTE: the dispatch span measures host time to *enqueue* the jitted
    # program (plus trace/lower cost on a cache miss) — jax dispatch is
    # async, so device compute lands in the caller's block/reap interval.
    with obs.span("exec.dispatch", bank=bank.name, slots=bank.n_members,
                  bitstream_length=bitstream_length), _donation_quiet(donate):
        outs = fn(*args, **kw)
    return list(outs)


def precompile_bank(bank: BankPlan, values_seq, keys, bitstream_length: int,
                    *, active=None, bitflip_rate: float = 0.0,
                    flip_keys=None, backend: str | None = None,
                    key_mode: str | None = None, batch_shapes=None,
                    decode: bool = False, device=None, donate: bool = False,
                    fault_model: FaultModel | None = None,
                    interpret: bool | None = None) -> None:
    """Trace, lower and compile the program ``execute_bank`` would run with
    the same arguments, without running it or touching ``device``.

    Raises what building the program raises — bad request values, a trace
    error, a kernel the compiler refuses — and nothing a device does.  The
    executable lands in jit's cache, so an ``execute_bank`` call with the
    same arguments only transfers and enqueues.  The serve engine calls it
    after a failed launch, to tell a program's errors from a device's.
    """
    fn, args, kw = _bank_call(bank, values_seq, keys, bitstream_length,
                              active, bitflip_rate, flip_keys, backend,
                              key_mode, batch_shapes, decode, donate,
                              fault_model, interpret)
    if device is not None:
        # Stand-ins committed to ``device``: the program placed as
        # execute_bank's device_put places it, with no transfer.
        sharding = jax.sharding.SingleDeviceSharding(device)
        args = _with_keys(args, lambda k: jax.ShapeDtypeStruct(
            k.shape, k.dtype, sharding=sharding))
    with obs.span("exec.precompile", bank=bank.name, slots=bank.n_members,
                  bitstream_length=bitstream_length), _donation_quiet(donate):
        fn.trace(*args, **kw).lower().compile()


def _bank_call(bank, values_seq, keys, bitstream_length, active,
               bitflip_rate, flip_keys, backend, key_mode, batch_shapes,
               decode, donate, fault_model, interpret):
    """Checked, packed arguments of one bank dispatch: ``(fn, args, kw)``,
    the stacked key rows at ``args[2]`` and the fault keys at ``args[3]``."""
    backend, key_mode = _check_modes(backend, key_mode)
    if backend == "reference":
        raise ValueError("execute_bank runs compiled BankPlans; use "
                         "execute()/execute_many() for the reference backend")
    n = bank.n_members
    if len(values_seq) != n:
        raise ValueError(f"values: got {len(values_seq)} for {n} slots")
    with obs.span("exec.pack_values", slots=n):
        values_seq, scalar_names = _pack_values_seq(values_seq)
    with obs.span("exec.stage_keys"):
        keys = _normalize_keys(keys, n)
        batch_shapes = _normalize_batch_shapes(batch_shapes, n, "slots")
        active = _normalize_active(active, n)
        fault_model = _check_fault_args(bitflip_rate, fault_model, flip_keys,
                                        "flip_keys")
        flip_keys = _fault_flip_keys(flip_keys, n, bitflip_rate, fault_model)
    args = (bank, values_seq, keys, flip_keys, bitstream_length,
            float(bitflip_rate), backend == "compiled_pallas", decode)
    kw = dict(key_mode=key_mode, batch_shapes=batch_shapes, active=active,
              scalar_names=scalar_names, fault_model=fault_model,
              megakernel=backend == "compiled_megakernel",
              interpret=interpret)
    return (_execute_bank_donating if donate else _execute_bank), args, kw


def _with_keys(args: tuple, f) -> tuple:
    """``args`` with ``f`` applied to its key rows and fault keys."""
    keys, flip_keys = args[2], args[3]
    return (*args[:2], f(keys), None if flip_keys is None else f(flip_keys),
            *args[4:])


@contextlib.contextmanager
def _donation_quiet(donate: bool):
    # Donation is best-effort: when no output can alias a key-row buffer
    # (the common case — outputs are packed words, not keys) XLA ignores it
    # and jax warns; that advisory is noise on a hot serving path.
    if not donate:
        yield
        return
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message="Some donated buffers were not")
        yield


# ---------------------------- host-side key staging --------------------------------

def _key_data_host(k) -> np.ndarray:
    # The public unwrap (jax.random.key_data) dispatches an XLA op per key —
    # at serving rates that is the single largest per-batch host cost.  A
    # typed key's raw buffer is reachable directly (same data, same bits).
    # Raw uint32 keys have no such buffer: the AttributeError sends
    # _stack_keys to its jnp.stack path.
    return np.asarray(k._base_array)


def _stack_keys(keys: list):
    """Stack per-slot PRNG keys into one (n,) key array, host-side.

    ``jnp.stack`` over typed keys dispatches one expand_dims per slot plus a
    concatenate; staging the raw key data through numpy collapses that to
    ONE device put, bit-identical to the stacked keys (same key data, same
    impl).  Repeated slot keys (the unbound-slot placeholder) unwrap once.
    """
    try:
        memo: dict[int, np.ndarray] = {}
        rows = []
        for k in keys:
            d = memo.get(id(k))
            if d is None:
                d = memo[id(k)] = _key_data_host(k)
            rows.append(d)
        return jax.random.wrap_key_data(jnp.asarray(np.stack(rows)),
                                        impl=jax.random.key_impl(keys[0]))
    except (TypeError, AttributeError):
        return jnp.stack(keys)


# ----------------------------- reference backend ----------------------------------

def _execute_reference(net: Netlist, values: dict[str, jax.Array],
                       key: jax.Array, bitstream_length: int,
                       bitflip_rate: float = 0.0,
                       flip_key: jax.Array | None = None,
                       key_mode: str = DEFAULT_KEY_MODE,
                       batch_shape: tuple[int, ...] | None = None,
                       fault_model: FaultModel | None = None) -> dict[str, jax.Array]:
    """Gate-by-gate interpreter: the oracle for the compiled plans.

    Stream generation honors the same ``key_mode`` as the compiled backends
    (the discipline lives in ``_gen_pi_streams``, upstream of interpretation),
    so reference and compiled outputs stay bit-for-bit comparable in either
    mode.  Fault injection (``bitflip_rate`` or its ``fault_model``
    generalization) applies at the same points with the same key splits as
    the compiled path."""
    streams = _gen_pi_streams(net.pis, values, key, bitstream_length,
                              key_mode=key_mode, batch_shape=batch_shape)

    fault_model = _check_fault_args(bitflip_rate, fault_model, flip_key)
    inject = _faults.injecting(bitflip_rate, fault_model)
    if inject:
        fk = flip_key if flip_key is not None else jax.random.key(0)
        fkeys = jax.random.split(fk, len(streams) + len(net.gates))
        for i, name in enumerate(sorted(streams)):
            streams[name] = _faults.apply_faults(fkeys[i], streams[name],
                                                 bitflip_rate, fault_model)

    if not net.is_sequential:
        # Snapshot the PI-stream count: gate outputs are appended to the env
        # below, and letting the flip-key index grow with it would silently
        # clamp past the end of ``fkeys`` and reuse the last key.
        n_streams = len(streams)
        for gi, g in enumerate(net.gates):
            out = bs.GATE_FNS[g.gtype](*[streams[i] for i in g.inputs])
            if inject:
                out = _faults.apply_faults(fkeys[n_streams + gi], out,
                                           bitflip_rate, fault_model)
            streams[g.output] = out
        return {o: streams[o] for o in net.outputs}

    # Sequential: iterate the combinational core over bitstream bits.
    state_pis = list(net.state_bindings.keys())
    # State-only recurrences have no streams to read the shape from.
    shape = (next(iter(streams.values())).shape if streams
             else (bitstream_length // bs.WORD_BITS,))  # (..., W)
    bl = bitstream_length

    def unpack_time_major(w):
        bits = bs.unpack_bits(w)                      # (..., W, 32)
        flat = bits.reshape(bits.shape[:-2] + (bl,))
        return jnp.moveaxis(flat, -1, 0)              # (BL, ...)

    time_streams = {k: unpack_time_major(v) for k, v in streams.items()}

    def step(state, xs):
        env = dict(xs) if xs is not None else {}
        for s_name in state_pis:
            env[s_name] = state[s_name]
        for g in net.gates:
            env[g.output] = bs.GATE_FNS[g.gtype](*[env[i] for i in g.inputs])
        new_state = {s: env[net.state_bindings[s][0]] for s in state_pis}
        outs = {o: env[o] for o in net.outputs}
        return new_state, outs

    init = {s: jnp.full(shape[:-1], jnp.uint32(round(net.state_bindings[s][1])))
            for s in state_pis}
    _, out_seq = jax.lax.scan(step, init, time_streams or None,
                              length=None if time_streams else bl)
    packed_outs = {}
    for o, seq in out_seq.items():
        seq = jnp.moveaxis(seq, 0, -1)                # (..., BL)
        bits = seq.reshape(seq.shape[:-1] + (bl // 32, 32))
        # Mask to bit 0 before packing: inverting gates (~x) leave garbage
        # in bits 1..31 of the per-step values, which pack_bits would sum
        # into other bit positions of the word.
        packed_outs[o] = bs.pack_bits(bits & jnp.uint32(1))
    if inject:
        for i, o in enumerate(sorted(packed_outs)):
            packed_outs[o] = _faults.apply_faults(fkeys[len(streams) + i],
                                                  packed_outs[o],
                                                  bitflip_rate, fault_model)
    return packed_outs
