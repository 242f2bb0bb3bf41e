"""PI stream generation: the BtoS front of every execution path.

Bottom layer of the executor stack (``streams`` <- ``dispatch`` <-
``exec_api`` <- the ``executor`` facade): given a plan's PrimaryInputs and
their values, produce the packed uint32 stochastic streams the logic passes
consume.  Two key disciplines (``key_mode``), honored identically by every
backend so reference and compiled stay bit-for-bit interchangeable:

  * ``"batched"`` (default): ONE fused threshold+pack pass generates all
    streams from the plan's stream table (``bs.generate_batch``) —
    correlation groups share a key lane, singles get one lane each.  Bank
    execution extends this bank-wide: every member's stream-table rows stack
    into one threshold tensor per distinct batch shape
    (``_gen_bank_streams``), the paper's bulk BtoS pass.
  * ``"legacy"``: one PRNG split per correlation group / single PI, one
    ``bs.generate*`` dispatch each — bit-exactly the pre-batching behavior,
    kept for reproducibility pins.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import bitstream as bs
from .gates import PIKind
from .plan import BankPlan, StreamTable, build_stream_table

#: Default backend for execute()/execute_value()/execute_binary().
DEFAULT_BACKEND = "compiled"

_BACKENDS = ("compiled", "compiled_pallas", "compiled_megakernel", "reference")

#: Default key discipline for PI-stream generation (see ``_gen_pi_streams``).
DEFAULT_KEY_MODE = "batched"

_KEY_MODES = ("batched", "legacy")


def _pi_shape(values: dict[str, jax.Array],
              batch_shape: tuple[int, ...] | None) -> tuple[int, ...]:
    """Common broadcast shape of the PI streams.

    Derived from the supplied values AND the caller-declared ``batch_shape``
    — so a netlist whose stream PIs are all const-valued (empty ``values``)
    can still generate batched streams for batched downstream use instead of
    silently falling back to scalar shape ``()``.
    """
    shapes = [jnp.shape(jnp.asarray(v)) for v in values.values()]
    if batch_shape is not None:
        shapes.append(tuple(batch_shape))
    return jnp.broadcast_shapes(*shapes) if shapes else ()


def _stack_table_values(table: StreamTable, values: dict[str, jax.Array],
                        shape: tuple[int, ...]) -> jax.Array:
    """Stack the stream table's row values into one (n_rows, *shape) tensor."""
    rows = []
    for vk, const in zip(table.value_keys, table.const_values):
        v = values[vk] if vk is not None else const
        rows.append(jnp.broadcast_to(jnp.asarray(v, jnp.float32), shape))
    return jnp.stack(rows)


def _gen_pi_streams(pis, values: dict[str, jax.Array], key: jax.Array,
                    bitstream_length: int, key_mode: str = DEFAULT_KEY_MODE,
                    batch_shape: tuple[int, ...] | None = None,
                    use_pallas: bool = False,
                    table: StreamTable | None = None,
                    word_window: tuple | None = None,
                    interpret: bool | None = None) -> dict[str, jax.Array]:
    """Generate packed streams for every PI, honoring correlation groups and
    independent-copy indices.  ``pis`` is any sequence of PrimaryInput.

    ``key_mode`` selects the key discipline (see module docstring).  The two
    modes differ bit-wise but are statistically equivalent (same Bernoulli
    marginals, same correlation structure).

    ``word_window=(start, n)`` (batched mode only) generates just words
    ``[start, start + n)`` of each stream — bit-identical to slicing the full
    streams, because the counter-based RNG indexes absolute bit positions.
    The chunked streaming executor regenerates each chunk's PI words this way
    instead of holding full-length streams live.  The legacy threefry
    discipline draws all words in one monolithic call and cannot window.
    ``interpret`` forwards to the Pallas SNG kernel (``use_pallas``).
    Its operations carry the name scope ``sc.sng`` in the device trace.
    """
    with jax.named_scope("sc.sng"):
        return _gen_pi_streams_impl(pis, values, key, bitstream_length,
                                    key_mode, batch_shape, use_pallas, table,
                                    word_window, interpret)


def _gen_pi_streams_impl(pis, values, key, bitstream_length, key_mode,
                         batch_shape, use_pallas, table, word_window,
                         interpret):
    shape = _pi_shape(values, batch_shape)
    if key_mode == "batched":
        if table is None:
            table = build_stream_table(pis)
        if not table.names:
            return {}
        ps = _stack_table_values(table, values, shape)
        words = bs.generate_batch(key, ps, bitstream_length,
                                  lanes=jnp.asarray(table.lanes, jnp.uint32),
                                  use_pallas=use_pallas,
                                  word_window=word_window,
                                  interpret=interpret)
        return {name: words[i] for i, name in enumerate(table.names)}
    if word_window is not None:
        raise ValueError("word_window requires key_mode='batched': legacy "
                         "threefry streams are not word-addressable")
    if key_mode != "legacy":
        raise ValueError(f"unknown key_mode {key_mode!r}; "
                         f"expected one of {_KEY_MODES}")

    streams: dict[str, jax.Array] = {}

    # Correlated groups share underlying uniforms.
    groups: dict[str, list] = {}
    singles: list = []
    for pi in pis:
        if pi.kind == PIKind.STATE:
            continue
        if pi.corr_group is not None:
            groups.setdefault(pi.corr_group, []).append(pi)
        else:
            singles.append(pi)

    n_keys = len(groups) + len(singles)
    keys = jax.random.split(key, max(n_keys, 1))
    ki = 0
    for gname, gpis in sorted(groups.items()):
        vals = []
        for pi in gpis:
            v = values[pi.value_key] if pi.value_key else pi.const_value
            vals.append(jnp.broadcast_to(jnp.asarray(v, jnp.float32), shape))
        outs = bs.generate_correlated(keys[ki], vals, bitstream_length)
        ki += 1
        for pi, o in zip(gpis, outs):
            streams[pi.name] = o
    for pi in singles:
        v = values[pi.value_key] if pi.value_key is not None else pi.const_value
        v = jnp.broadcast_to(jnp.asarray(v, jnp.float32), shape)
        streams[pi.name] = bs.generate(keys[ki], v, bitstream_length)
        ki += 1
    return streams


def _gen_bank_streams(bank: BankPlan, values_seq, keys, bitstream_length: int,
                      key_mode: str, use_pallas: bool,
                      batch_shapes, active=None,
                      interpret: bool | None = None) -> list[dict[str, jax.Array]]:
    """Per-member PI streams for a whole bank (list indexed by member).

    Batched key mode is the paper's bulk BtoS pass bank-wide: every member's
    stream-table rows stack into ONE threshold tensor per distinct batch
    shape and generate in one fused SNG pass — instead of one dispatch per
    PI per member.  Each row's randomness is keyed by (member key, fixed
    key-lane index), independent of the stacking, so a merged run stays
    bit-identical to a loop of per-member ``execute`` calls in the same mode.

    ``active`` (None = all) masks padded template slots: inactive members
    contribute NO rows to the fused SNG pass — their PI streams are zero
    words (value-0.0 constants, nearly free), just enough to keep the merged
    logic passes well-formed.  Active members' streams are untouched by the
    masking, so padded execution stays bit-identical per bound slot.
    ``interpret`` forwards to the Pallas SNG kernel (``use_pallas``).
    Its operations carry the name scope ``sc.sng``, as in ``_gen_pi_streams``.
    """
    with jax.named_scope("sc.sng"):
        return _gen_bank_streams_impl(bank, values_seq, keys,
                                      bitstream_length, key_mode, use_pallas,
                                      batch_shapes, active, interpret)


def _gen_bank_streams_impl(bank, values_seq, keys, bitstream_length,
                           key_mode, use_pallas, batch_shapes, active,
                           interpret):
    n = bank.n_members
    streams: list[dict[str, jax.Array]] = [{} for _ in range(n)]
    w = bs.n_words(bitstream_length)

    def masked(i: int) -> bool:
        return active is not None and not active[i]

    def zero_fill(i: int) -> dict[str, jax.Array]:
        return {nm: jnp.zeros((w,), jnp.uint32)
                for nm in bank.members[i].stream_table.names}

    if key_mode != "batched":
        for i, plan in enumerate(bank.members):
            if masked(i):
                streams[i] = zero_fill(i)
                continue
            streams[i] = _gen_pi_streams(
                plan.pis, values_seq[i], keys[i], bitstream_length,
                key_mode=key_mode,
                batch_shape=batch_shapes[i] if batch_shapes else None)
        return streams

    # Group member tables by broadcast shape; one fused SNG pass per shape.
    buckets: dict[tuple[int, ...], list[tuple[int, jax.Array, jax.Array]]] = {}
    for i, plan in enumerate(bank.members):
        table = plan.stream_table
        if not table.names:
            continue
        if masked(i):
            streams[i] = zero_fill(i)
            continue
        shape = _pi_shape(values_seq[i],
                          batch_shapes[i] if batch_shapes else None)
        ps = _stack_table_values(table, values_seq[i], shape)
        seeds = bs.stream_row_seeds(keys[i],
                                    jnp.asarray(table.lanes, jnp.uint32))
        buckets.setdefault(shape, []).append((i, ps, seeds))
    for entries in buckets.values():
        ps = jnp.concatenate([e[1] for e in entries])
        seeds = jnp.concatenate([e[2] for e in entries])
        words = bs.generate_batch_seeded(seeds, ps, bitstream_length,
                                         use_pallas=use_pallas,
                                         interpret=interpret)
        off = 0
        for i, ps_i, _ in entries:
            names = bank.members[i].stream_table.names
            for k, nm in enumerate(names):
                streams[i][nm] = words[off + k]
            off += len(names)
    return streams
