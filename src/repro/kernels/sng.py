"""Pallas kernel: batched stochastic number generation over a stream table.

The BtoS step of the paper writes *all* operand streams into subarray rows in
bulk before any gate pass runs (Sec. 2-3 / Fig. 8) — and for in-memory SC it
is stream generation, not the logic passes, that dominates end-to-end cost
(Khatamifard et al.; Razi et al.).  This kernel is the TPU translation of
that bulk write: ONE fused threshold+pack pass generates every primary-input
stream of a compiled plan (or a whole bank of plans) from a stacked
threshold table, instead of one dispatch per stream.

Layout: the *stream table* (``core.plan.StreamTable``) stacks the plan's
non-state PIs into rows.  Row ``i`` carries a pre-mixed per-row seed
(``common.mix_seed(seed, lane_i)``); rows with equal key-lane index share
their uniforms — that is how correlation groups (XOR = |a-b|, Fig. 4(c))
ride through the same batched pass as the independent streams.

The kernel packs by compare-and-accumulate over the 32 lane shifts: the
(…, W, 32) unpacked bit tensor is never materialized (32x less live memory
than the threshold-then-pack formulation).  Counters derive from global
(element, bit) indices, so output is tiling-independent and bit-identical to
``ref.sng_words_ref`` — the jnp fallback the executor uses by default
(``use_pallas`` opts into the kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .common import (WORD_BITS, hash_u32, mix_seed, resolve_interpret,
                     threshold_u32)


def lane_seeds(seed: jax.Array, lanes: jax.Array) -> jax.Array:
    """Per-row mixed seeds for a stream table: (N,) lanes -> (N,) seeds."""
    return mix_seed(jnp.asarray(seed, jnp.uint32),
                    jnp.asarray(lanes, jnp.uint32))


def _kernel(seed_ref, thr_ref, o_ref, *, n_words: int, be: int, rb: int):
    # Word-major tile: elements run along the 128 lanes, words along the
    # sublanes, so a row's thresholds broadcast down the sublanes and the
    # (W, be) tile stays lane-dense for any stream length.
    j = pl.program_id(1)
    elem = j * be + jax.lax.broadcasted_iota(jnp.uint32, (n_words, be), 1)
    word = jax.lax.broadcasted_iota(jnp.uint32, (n_words, be), 0)
    base = (elem * jnp.uint32(n_words) + word) * jnp.uint32(WORD_BITS)
    for r in range(rb):                                   # rows of this block
        s = seed_ref[pl.ds(r, 1), :]                      # (1, be) row seed
        thr = thr_ref[pl.ds(r, 1), :]                     # (1, be)
        acc = jnp.zeros((n_words, be), jnp.uint32)
        for t in range(WORD_BITS):
            h = hash_u32((base + jnp.uint32(t)) ^ s)
            acc = acc | ((h < thr).astype(jnp.uint32) << jnp.uint32(t))
        o_ref[r] = acc


@functools.partial(jax.jit, static_argnames=("n_words", "use_pallas",
                                             "block_elems", "interpret",
                                             "total_words"))
def sng_words(row_seeds: jax.Array, thr: jax.Array, n_words: int,
              use_pallas: bool = False, block_elems: int = 256,
              interpret: bool | None = None,
              word_offset: jax.Array | None = None,
              total_words: int | None = None) -> jax.Array:
    """Batched SNG over a stream table: (N, B) thresholds -> (N, B, W) words.

    ``row_seeds``: (N,) pre-mixed per-row seeds (``lane_seeds``); rows with
    equal seed share their uniforms (correlation groups decode exact |a-b|
    under XOR).  ``thr``: (N, B) uint32 compare thresholds.  The jnp fallback
    (``use_pallas=False``, the executor default) and the Pallas kernel are
    bit-identical; ``interpret`` resolves through ``common.resolve_interpret``
    (None: compiled on a TPU, elsewhere interpreted with a warning).

    The kernel's blocks meet the TPU's (8, 128) tiling rule at any table
    size: each grid step reads ``min(8, N)`` rows — their thresholds as a
    ``(rows, block_elems)`` tile, their seeds as one such tile repeated along
    the lanes (Mosaic cannot broadcast a scalar over sublanes and lanes at
    once) — so its VMEM use does not grow with the table, and writes those
    rows word-major, ``(N, W, B)`` with elements on the lanes.  The final
    axis swap to ``(N, B, W)`` and ``common.lane_tiles``' swap back compile
    to layout bitcasts, not copies (``tests/test_tpu_compile.py``).
    ``block_elems`` must be a multiple of 128, or cover ``B``, on the chip;
    interpret mode takes any block.

    ``word_offset``/``total_words`` request a word *window* of a conceptual
    ``total_words``-long stream (see ``ref.sng_words_ref``) — exact because
    the counter is the absolute bit index.  Windowed generation always runs
    the jnp path: ``word_offset`` is typically a traced scan index, which the
    grid-blocked Pallas kernel cannot take as a static.
    """
    total = n_words if total_words is None else total_words
    if thr.shape[-1] * total * WORD_BITS > 1 << 32:
        # Bit counters are uint32 per (row, element, bit): past 2^32 bits per
        # row they wrap, silently duplicating uniforms between far-apart
        # elements (streams assumed independent become perfectly correlated).
        # The legacy threefry discipline has no such cliff, so refuse loudly.
        raise ValueError(
            f"batched SNG counter space exhausted: {thr.shape[-1]} elements x "
            f"{total * WORD_BITS} bits > 2^32 bits per stream row; shard "
            "the batch across keys or use key_mode='legacy'")
    windowed = word_offset is not None or total != n_words
    if not use_pallas or windowed:
        return ref.sng_words_ref(row_seeds, thr, n_words,
                                 word_offset=word_offset, total_words=total)
    n, b = thr.shape
    be = min(block_elems, b)
    rb = min(8, n)
    grid = (pl.cdiv(n, rb), pl.cdiv(b, be))
    words = pl.pallas_call(
        functools.partial(_kernel, n_words=n_words, be=be, rb=rb),
        grid=grid,
        in_specs=[pl.BlockSpec((rb, be), lambda i, j: (i, 0)),
                  pl.BlockSpec((rb, be), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((rb, n_words, be), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, n_words, b), jnp.uint32),
        interpret=resolve_interpret(interpret),
        name="sng_words",
    )(jnp.broadcast_to(row_seeds.astype(jnp.uint32)[:, None], (n, be)), thr)
    return jnp.swapaxes(words, 1, 2)


@functools.partial(jax.jit, static_argnames=("bitstream_length", "seed",
                                             "block", "interpret"))
def sng_pack(p: jax.Array, bitstream_length: int = 256, seed: int = 0,
             block: int = 256, interpret: bool = True) -> jax.Array:
    """p: (N,) float in [0,1] -> (N, BL//32) packed uint32 bitstreams.

    Single-row degenerate case of ``sng_words`` (one table row, key lane 0,
    every element of ``p`` a batch element) — equals ``ref.sng_pack_ref``.
    """
    n_words = bitstream_length // WORD_BITS
    seeds = lane_seeds(jnp.uint32(seed), jnp.zeros((1,), jnp.uint32))
    thr = threshold_u32(p.astype(jnp.float32))[None, :]
    return sng_words(seeds, thr, n_words, use_pallas=True, block_elems=block,
                     interpret=interpret)[0]
