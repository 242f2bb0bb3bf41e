"""Shared kernel utilities: in-kernel counter-based RNG and packing.

The MTJ's intrinsic stochastic switching generates bitstream bits *in place*,
fused with computation (paper Section 4-1).  The TPU analogue is a
counter-based hash RNG evaluated inside the kernel (VMEM-resident, no HBM
traffic for randomness).  We use the murmur3/splitmix finalizer — statistical
quality is ample for SC (independence across counters is what matters), and
keeping it in plain jnp means the Pallas kernel and the ref.py oracle compute
*bit-identical* streams, enabling exact equality tests.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

WORD_BITS = 32
# Murmur3 finalizer multipliers (``hash_u32``).
MURMUR_K1 = 0x85EBCA6B
MURMUR_K2 = 0xC2B2AE35


class InterpretModeWarning(UserWarning):
    """A Pallas kernel was asked to pick its own mode off the chip and runs
    in the (slow, host-side) interpreter instead of compiled."""


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas interpret flag for one kernel call.

    An explicit ``True``/``False`` passes through (tests ask for
    ``interpret=True``).  ``None`` compiles the kernel on a TPU; on any other
    default backend it falls back to interpret mode and says so with an
    ``InterpretModeWarning`` — a chip that failed to initialise must not
    pass silently for a compiled run.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    warnings.warn(
        f"Pallas kernel runs in interpret mode: the default JAX backend is "
        f"{backend!r}, not 'tpu' (pass interpret=True to ask "
        "for it explicitly)", InterpretModeWarning, stacklevel=3)
    return True


def lane_tiles(x: jax.Array) -> jax.Array:
    """View packed words of any shape as ``(lead, sublanes, lanes)`` tiles.

    The longer of the last two axes goes on the lanes, so a frame-sized
    batch of short streams — ``(76800, 32)`` at BL=1024 — is handed to a
    kernel as ``(32, 76800)`` instead of a 32-lane array padded to 128
    lanes (4x the HBM).  Every kernel on these tiles is elementwise, so the
    view changes no bit; ``from_lane_tiles`` undoes it.
    """
    if x.ndim < 2:
        return x.reshape(1, 1, -1)
    r, w = x.shape[-2:]
    x3 = x.reshape(-1, r, w)
    return jnp.swapaxes(x3, 1, 2) if w < r else x3


def from_lane_tiles(y: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """Inverse of ``lane_tiles`` for an array of logical ``shape``."""
    if len(shape) >= 2 and shape[-1] < shape[-2]:
        y = jnp.swapaxes(y, -1, -2)
    return y.reshape(shape)


def hash_u32(x: jax.Array) -> jax.Array:
    """Murmur3 finalizer: uint32 -> well-mixed uint32."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(MURMUR_K1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(MURMUR_K2)
    x = x ^ (x >> 16)
    return x


def threshold_u32(p: jax.Array) -> jax.Array:
    """Probability in [0,1] -> uint32 compare threshold (the BtoS LUT analogue).

    Clamped on the integer side: float32 cannot represent 2^32 - 1 (it rounds
    to 2^32), so a float-side minimum is a no-op and the out-of-range
    float->uint32 cast it was meant to prevent is undefined across XLA
    backends.  Anything that rounds to >= 2^32 maps to 0xFFFFFFFF instead
    (p=1.0 covers all but one value in 2^32 — the same convention as
    ``core.bitstream._threshold_u32``).
    """
    scaled = jnp.round(jnp.clip(p, 0.0, 1.0).astype(jnp.float32) * 4294967296.0)
    return jnp.where(scaled >= jnp.float32(4294967296.0), jnp.uint32(0xFFFFFFFF),
                     scaled.astype(jnp.uint32))


def mix_seed(seed: jax.Array, lane: jax.Array) -> jax.Array:
    """Derive a per-stream-row mixed seed from (seed, key-lane index).

    Rows with equal lane share their uniforms (correlation groups); rows with
    distinct lanes are statistically independent.  The mix is applied once
    outside the generation loop, so the hot path hashes only the bit counter.
    """
    return hash_u32(hash_u32(seed.astype(jnp.uint32)) ^ lane.astype(jnp.uint32))


def gen_packed_bits_seeded(mixed_seed: jax.Array, base_index: jax.Array,
                           thr: jax.Array) -> jax.Array:
    """Generate one packed uint32 word of Bernoulli bits per element.

    ``mixed_seed``: pre-mixed per-row seed (see ``mix_seed``), broadcastable
    against ``base_index``.  ``base_index``: uint32 tensor of *bit-space* base
    counters (flat element index * 32).  ``thr``: uint32 compare thresholds
    (``threshold_u32``), broadcastable against ``base_index``.  Bit ``t`` of
    the output word is 1 iff hash(base+t ^ seed) < thr.
    """
    lanes = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    ctr = base_index[..., None] + lanes          # (..., 32)
    r = hash_u32(ctr ^ mixed_seed[..., None])
    bits = (r < thr[..., None]).astype(jnp.uint32)
    return jnp.sum(bits << lanes, axis=-1, dtype=jnp.uint32)


def gen_packed_bits(seed: jax.Array, base_index: jax.Array, p: jax.Array) -> jax.Array:
    """Generate one packed uint32 word of Bernoulli(p) bits per element.

    ``base_index``: uint32 tensor of *bit-space* base counters (flat element
    index * 32), broadcastable against ``p``.  Bit ``t`` of the output word is
    1 with probability ``p``, independently across (seed, counter) pairs.
    """
    mixed = jnp.broadcast_to(hash_u32(seed.astype(jnp.uint32)), base_index.shape)
    return gen_packed_bits_seeded(mixed, base_index, threshold_u32(p))


def popcount(words: jax.Array) -> jax.Array:
    return jax.lax.population_count(words).astype(jnp.int32)
