"""Public jit'd entry points for the SC kernels.

``use_pallas`` selects the Pallas path (compiled on TPU; elsewhere interpret
mode, with an ``InterpretModeWarning``); the ref path is the pure-jnp
oracle.  Both compute bit-identical results (same counter-based RNG), so the
switch is purely an execution-strategy choice.
"""
from __future__ import annotations

import jax

from . import ref
from .common import resolve_interpret
from .packed_logic import packed_logic
from .popcount_tree import popcount_hier
from .sc_matmul import sc_matmul as _sc_matmul_pallas
from .sng import sng_pack as _sng_pallas, sng_words as _sng_words


def sc_matmul(a: jax.Array, w: jax.Array, bitstream_length: int = 256,
              seed: int = 0, use_pallas: bool = True, bm: int = 8,
              bn: int = 128, bk: int = 128) -> jax.Array:
    if use_pallas:
        return _sc_matmul_pallas(a, w, bitstream_length, seed, bm=bm, bn=bn,
                                 bk=bk, interpret=resolve_interpret(None))
    return ref.sc_matmul_ref(a, w, bitstream_length, seed)


def sng(p: jax.Array, bitstream_length: int = 256, seed: int = 0,
        use_pallas: bool = True) -> jax.Array:
    if use_pallas:
        flat = p.reshape(-1)
        out = _sng_pallas(flat, bitstream_length, seed,
                          interpret=resolve_interpret(None))
        return out.reshape(p.shape + (bitstream_length // 32,))
    return ref.sng_pack_ref(p, bitstream_length, seed)


def sng_table(row_seeds: jax.Array, thr: jax.Array, bitstream_length: int = 256,
              use_pallas: bool = True) -> jax.Array:
    """Batched stream-table SNG: (N,) seeds + (N, B) thresholds -> (N, B, W)."""
    if bitstream_length % 32 != 0:
        raise ValueError(f"bitstream length {bitstream_length} must be a "
                         "multiple of 32")
    # sng_words routes to its jnp path (sng_words_jnp) when use_pallas=False
    # and resolves interpret mode (common.resolve_interpret) otherwise.
    return _sng_words(row_seeds, thr, bitstream_length // 32,
                      use_pallas=use_pallas)


def logic(op: str, *args: jax.Array, use_pallas: bool = True) -> jax.Array:
    if use_pallas:
        return packed_logic(op, *args, interpret=resolve_interpret(None))
    return ref.sc_eltwise_ref(op, *args)


def stob_counts(words: jax.Array, use_pallas: bool = True) -> jax.Array:
    if use_pallas:
        return popcount_hier(words, interpret=resolve_interpret(None))
    return ref.popcount_hier_ref(words, group=16)
