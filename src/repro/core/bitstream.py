"""Packed stochastic bitstreams (unipolar encoding) in JAX.

A stochastic number (SN) of value ``p`` in [0, 1] is a bitstream whose bits are
i.i.d. Bernoulli(p) (Section 2-3).  We store bitstreams *packed*, 32 bits per
``uint32`` word, so every bitwise op processes 32 bitstream bits per lane —
this is the TPU translation of the paper's bit-parallelism across subarrays
(DESIGN.md Section 2).

Shapes: a bitstream tensor for values of shape ``S`` with bitstream length
``BL`` is ``S + (BL // 32,)`` of dtype uint32.

Generation uses counter-based PRNG (stands in for the MTJ intrinsic
stochastic switching of Eqs. (1)-(2)); correlated streams share their
underlying uniforms so that XOR computes exact |a-b| (Fig. 4(c)/5(c)).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32
_LANE_SHIFTS = np.arange(WORD_BITS, dtype=np.uint32)


def n_words(bitstream_length: int) -> int:
    if bitstream_length % WORD_BITS != 0:
        raise ValueError(f"bitstream length {bitstream_length} must be a multiple of {WORD_BITS}")
    return bitstream_length // WORD_BITS


def _threshold_u32(p: jax.Array) -> jax.Array:
    """Map probability p in [0,1] to a uint32 compare threshold.

    This is the digital analogue of the BtoS voltage-pulse LUT: the value is
    quantized to a threshold such that P(rand_u32 < threshold) = p.
    """
    dt = jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32
    p = jnp.clip(p.astype(dt), 0.0, 1.0)
    scaled = jnp.round(p * dt(4294967296.0))
    # 2^32 is not representable in uint32 — and float32 cannot even hold
    # 2^32 - 1 (it rounds to 2^32), so a float-side minimum is a no-op and the
    # out-of-range float->uint32 cast it was meant to prevent is undefined
    # across XLA backends.  Clamp on the integer side instead: anything that
    # rounded to >= 2^32 maps to 0xFFFFFFFF, so p=1.0 gives an (almost-surely)
    # all-ones stream — threshold 0xFFFFFFFF covers all but one value in 2^32.
    return jnp.where(scaled >= dt(4294967296.0), jnp.uint32(0xFFFFFFFF),
                     scaled.astype(jnp.uint32))


def _uniform_u32(key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    return jax.random.bits(key, shape=shape, dtype=jnp.uint32)


@partial(jax.jit, static_argnames=("bitstream_length",))
def generate(key: jax.Array, p: jax.Array, bitstream_length: int) -> jax.Array:
    """Generate packed bitstreams: shape p.shape + (BL//32,) uint32.

    Models the stochastic-number-generation step: each bit is '1' with
    probability p, independently (MTJ stochastic write per cell).
    """
    w = n_words(bitstream_length)
    u = _uniform_u32(key, p.shape + (w, WORD_BITS))
    bits = (u < _threshold_u32(p)[..., None, None]).astype(jnp.uint32)
    return pack_bits(bits)


@partial(jax.jit, static_argnames=("bitstream_length",))
def generate_correlated(key: jax.Array, ps: tuple[jax.Array, ...] | list[jax.Array],
                        bitstream_length: int) -> tuple[jax.Array, ...]:
    """Generate maximally-correlated packed streams for several values.

    All streams share the same underlying uniforms (same RNG cells written
    with different pulse amplitudes, in paper terms), so
    XOR(stream_a, stream_b) has value exactly |a - b| in expectation.
    Values must be broadcast-compatible.

    The per-stream thresholds are stacked into one leading axis and compared
    against the shared uniforms in a single broadcast — bit-identical to (but
    one dispatch instead of N of) thresholding each stream separately.
    """
    shape = jnp.broadcast_shapes(*[jnp.shape(p) for p in ps])
    w = n_words(bitstream_length)
    u = _uniform_u32(key, shape + (w, WORD_BITS))
    stacked = jnp.stack([jnp.broadcast_to(jnp.asarray(p), shape) for p in ps])
    thr = _threshold_u32(stacked)[..., None, None]        # (N, *shape, 1, 1)
    words = pack_bits((u[None] < thr).astype(jnp.uint32))  # (N, *shape, W)
    return tuple(words[i] for i in range(len(ps)))


# --- batched stream-table generation (the bulk BtoS pass) -------------------------
#
# The paper writes ALL operand streams into subarray rows in bulk before any
# gate pass runs (Sec. 2-3, Fig. 8); stream generation, not logic, dominates
# end-to-end SC cost.  ``generate_batch`` is that bulk write: every stream of
# a compiled plan's stream table (core/plan.py) generates in ONE fused
# threshold+pack pass over a stacked (N, *batch) value tensor, using the
# counter-based RNG of kernels/common.py (murmur3 finalizer) instead of one
# threefry call per stream.  Rows with equal key-lane index share their
# uniforms, so correlation groups ride through the same pass.  This is the
# ``key_mode="batched"`` discipline (executor.py): streams differ bit-wise
# from the legacy per-PI threefry splits but are statistically equivalent.
# The jnp path (``kernels.sng.sng_words_jnp``, the default) and the Pallas
# kernel are bit-identical, both tested against ``ref.sng_words_ref``.

def stream_row_seeds(key: jax.Array, lanes) -> jax.Array:
    """Mixed per-row seeds for a stream table: row i <- hash(key seed, lane_i).

    A row's stream depends only on (key, lane, element, bit), never on how
    many other rows are generated alongside it — so concatenating tables
    (bank-level generation) or splitting them changes nothing bit-wise.
    """
    from ..kernels.sng import lane_seeds
    seed = jax.random.bits(key, (), jnp.uint32)
    return lane_seeds(seed, jnp.asarray(lanes, jnp.uint32))


def generate_batch_seeded(row_seeds: jax.Array, ps: jax.Array,
                          bitstream_length: int,
                          use_pallas: bool = False,
                          word_window: tuple | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """Batched SNG from pre-mixed row seeds: ps (N, *batch) -> (N, *batch, W).

    Thresholds and packs by compare-and-accumulate over the 32 lane shifts —
    the (..., W, 32) unpacked uniform tensor of ``generate`` is never
    materialized.  The default is the jnp path (``kernels.sng.sng_words_jnp``);
    ``use_pallas`` routes through the fused Pallas SNG kernel instead, and both
    are bit-identical to the oracle ``ref.sng_words_ref``; ``interpret``
    forwards to the kernel (None: compiled on a TPU).

    ``word_window=(start, n)`` generates only words ``[start, start + n)`` of
    the ``bitstream_length``-long streams — bit-identical to slicing a
    whole-stream call, because the counter-based RNG indexes absolute bit
    positions.  ``start`` may be traced (a scan chunk index); ``n`` must be
    static.  This is what lets the chunked streaming executor regenerate PI
    streams per chunk instead of holding them at full length.
    """
    from ..kernels.sng import sng_words
    w = n_words(bitstream_length)
    ps = jnp.asarray(ps)
    thr = _threshold_u32(ps).reshape(ps.shape[0], -1)      # (N, B)
    if word_window is None:
        words = sng_words(row_seeds, thr, w, use_pallas=use_pallas,
                          interpret=interpret)
        return words.reshape(ps.shape + (w,))
    start, n_win = word_window
    words = sng_words(row_seeds, thr, n_win, use_pallas=use_pallas,
                      word_offset=start, total_words=w)
    return words.reshape(ps.shape + (n_win,))


def generate_batch(key: jax.Array, ps: jax.Array, bitstream_length: int,
                   lanes=None, use_pallas: bool = False,
                   word_window: tuple | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """Generate N packed streams in one pass: ps (N, *batch) -> (N, *batch, W).

    ``lanes`` (default ``arange(N)``) assigns each row its key-lane index:
    rows with distinct lanes are independent; rows sharing a lane share their
    underlying uniforms (a correlation group — XOR of two such rows decodes
    exact |a - b|).  ``word_window`` and ``interpret`` as in
    ``generate_batch_seeded``.
    """
    ps = jnp.asarray(ps)
    if lanes is None:
        lanes = jnp.arange(ps.shape[0], dtype=jnp.uint32)
    return generate_batch_seeded(stream_row_seeds(key, lanes), ps,
                                 bitstream_length, use_pallas=use_pallas,
                                 word_window=word_window, interpret=interpret)


def pack_bits(bits: jax.Array) -> jax.Array:
    """Pack a (..., W, 32) {0,1} tensor into (..., W) uint32 words."""
    shifts = jnp.asarray(_LANE_SHIFTS)
    return jnp.sum(bits.astype(jnp.uint32) << shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array) -> jax.Array:
    """Unpack (..., W) uint32 words into (..., W, 32) {0,1} uint32 bits."""
    shifts = jnp.asarray(_LANE_SHIFTS)
    return (words[..., None] >> shifts) & jnp.uint32(1)


def popcount(words: jax.Array) -> jax.Array:
    """Total number of set bits along the last (word) axis.

    This is the StoB conversion (Section 2-3 step 3): counting ones recovers
    the binary value.  ``lax.population_count`` is the per-word popcount; the
    sum over words mirrors the local-accumulator -> global-accumulator
    hierarchy of the Stoch-IMC architecture (Fig. 8).
    """
    per_word = jax.lax.population_count(words)
    return jnp.sum(per_word.astype(jnp.int32), axis=-1)


def to_value(words: jax.Array, bitstream_length: int) -> jax.Array:
    """Decode a packed bitstream back to its unipolar value in [0, 1]."""
    return popcount(words).astype(jnp.float32) / jnp.float32(bitstream_length)


# --- packed boolean algebra (the IMC primitive gates) ---------------------------

def not_(a: jax.Array) -> jax.Array:
    return ~a


def buff(a: jax.Array) -> jax.Array:
    return a


def and_(a: jax.Array, b: jax.Array) -> jax.Array:
    return a & b


def nand(a: jax.Array, b: jax.Array) -> jax.Array:
    return ~(a & b)


def or_(a: jax.Array, b: jax.Array) -> jax.Array:
    return a | b


def nor(a: jax.Array, b: jax.Array) -> jax.Array:
    return ~(a | b)


def xor(a: jax.Array, b: jax.Array) -> jax.Array:
    # Not an IMC primitive: realized as AND(NAND(a,b), OR(a,b)) in netlists.
    return a ^ b


def mux(a: jax.Array, b: jax.Array, sel: jax.Array) -> jax.Array:
    """Scaled addition (Fig. 4(a)): out = sel ? a : b, value = s*a + (1-s)*b."""
    return (a & sel) | (b & ~sel)


def maj3(a: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    return (a & b) | (a & c) | (b & c)


def maj5(a, b, c, d, e) -> jax.Array:
    # Majority-of-5 as a boolean identity over packed words.
    ab, ac, ad, ae = a & b, a & c, a & d, a & e
    bc, bd, be = b & c, b & d, b & e
    cd, ce, de = c & d, c & e, d & e
    return (
        (ab & c) | (ab & d) | (ab & e) | (ac & d) | (ac & e) | (ad & e)
        | (bc & d) | (bc & e) | (bd & e) | (cd & e)
    )


GATE_FNS = {
    "NOT": not_,
    "BUFF": buff,
    "AND": and_,
    "NAND": nand,
    "OR": or_,
    "NOR": nor,
    "XOR": xor,
    "MAJ3": maj3,
    "MAJ5": maj5,
    "NMAJ3": lambda a, b, c: ~maj3(a, b, c),
    "NMAJ5": lambda a, b, c, d, e: ~maj5(a, b, c, d, e),
}
