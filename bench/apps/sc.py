"""Plain stochastic-computing semantics the references are written in.

A stochastic number of value ``p`` is a bitstream of ``BL`` bits packed 32 to
a ``uint32`` word, bit ``i`` set with probability ``p``.  The key discipline
the system under test documents (``core/streams.py``, batched key mode):

* the request's PRNG key gives one 32-bit seed, ``jax.random.bits(key, ())``;
* each stream row ``r`` of a circuit has a key lane; its row seed is
  ``h(h(seed) ^ lane)``, with ``h`` the murmur3 finalizer;
* bit ``t`` of word ``w`` of batch element ``b`` is set iff
  ``h(((b * W + w) * 32 + t) ^ row_seed) < round(p * 2**32)``, the threshold
  clamped to ``2**32 - 1``.

Rows sharing a lane share their uniforms (a correlation group: XOR of two
such rows decodes ``|a - b|``).  This module imports nothing of the program;
it is the yardstick every reference in ``bench/apps`` builds on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WORD_BITS = 32
_TWO32 = 4294967296.0


def murmur(x: jax.Array) -> jax.Array:
    """Murmur3 32-bit finalizer."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def thresholds(p: jax.Array, bf16: bool = False) -> jax.Array:
    """Probabilities -> uint32 compare thresholds.

    ``bf16`` rounds the probabilities to bfloat16 first: the control, one
    precision step below the float32 the configurations state.
    """
    p = jnp.asarray(p, jnp.float32)
    if bf16:
        p = p.astype(jnp.bfloat16).astype(jnp.float32)
    scaled = jnp.round(jnp.clip(p, 0.0, 1.0) * jnp.float32(_TWO32))
    return jnp.where(scaled >= jnp.float32(_TWO32), jnp.uint32(0xFFFFFFFF),
                     scaled.astype(jnp.uint32))


def seed_of(key_data: jax.Array) -> jax.Array:
    """The request's 32-bit stream seed from its raw threefry key data."""
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32))
    return jax.random.bits(key, (), jnp.uint32)


def streams(seed: jax.Array, lanes: jax.Array, probs: jax.Array,
            bitstream_length: int, elem0=0, bf16: bool = False) -> jax.Array:
    """Packed streams of a block of batch elements: ``(rows, B, W)`` uint32.

    ``lanes``: ``(rows,)`` key lanes; ``probs``: ``(rows, B)`` values;
    ``elem0``: the block's first batch-element index in the whole request.
    """
    n_words = bitstream_length // WORD_BITS
    b = probs.shape[1]
    elem = jnp.asarray(elem0, jnp.uint32) + jnp.arange(b, dtype=jnp.uint32)
    base = ((elem[:, None] * jnp.uint32(n_words)
             + jnp.arange(n_words, dtype=jnp.uint32)[None, :])
            * jnp.uint32(WORD_BITS))                          # (B, W)
    row_seed = murmur(murmur(seed) ^ lanes.astype(jnp.uint32))  # (rows,)
    thr = thresholds(probs, bf16)[:, :, None]                 # (rows, B, 1)
    acc = jnp.zeros(probs.shape + (n_words,), jnp.uint32)
    for t in range(WORD_BITS):
        u = murmur((base[None] + jnp.uint32(t)) ^ row_seed[:, None, None])
        acc = acc | ((u < thr).astype(jnp.uint32) << jnp.uint32(t))
    return acc


def mux(a, b, sel):
    """Scaled addition: ``sel ? a : b`` bitwise, value ``s*a + (1-s)*b``."""
    return (a & sel) | (b & ~sel)


def mean_tree(leaves: list, selects) -> jax.Array:
    """Balanced MUX mean tree over ``leaves``; ``selects`` yields the
    value-0.5 select stream of each pair, level by level, left to right."""
    level = list(leaves)
    while len(level) > 1:
        nxt = [mux(level[i], level[i + 1], next(selects))
               for i in range(0, len(level) - 1, 2)]
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return level[0]


def decode(words: jax.Array, bitstream_length: int) -> jax.Array:
    """Unipolar value of packed streams: ones over ``BL``, float32."""
    ones = jnp.sum(jax.lax.population_count(words).astype(jnp.int32), -1)
    return ones.astype(jnp.float32) / jnp.float32(bitstream_length)
