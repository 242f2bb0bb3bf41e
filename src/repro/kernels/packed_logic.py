"""Pallas kernel: bit-parallel packed logic over uint32 bitstream words.

TPU mapping of the paper's intra-subarray SIMD gate execution: one VPU
bitwise op processes a whole VMEM tile = (rows x words x 32) bitstream bits —
the "subarray" of DESIGN.md §2.  The MUX (scaled addition) fuses 4 gates
(NOT + 2xNAND + NAND) into one pass, where the 2T-1MTJ method takes 4 cycles;
fusion is the beyond-paper win available on TPU (no per-gate cell writes).

Per-input complement masks (``neg``) are folded into the kernel itself: an
absorbed lone NOT costs zero extra passes AND zero extra XLA ops — the
complement happens on the VMEM-resident tile, not as a separate full-tensor
pass before the pallas_call.

Operands of any shape are viewed as ``common.lane_tiles`` — the longer of
the last two axes on the 128 lanes — so a frame of short streams stays
lane-dense in HBM and VMEM.  Block shapes: (8k sublanes, 128k lanes), or the
full extent, to match the (8, 128) vreg tiling for 32-bit types.
``interpret=None`` (the default) compiles on a TPU and elsewhere interprets
with a warning (``common.resolve_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import from_lane_tiles, lane_tiles, resolve_interpret

_OPS1 = {"not"}
_OPS2 = {"and", "nand", "or", "nor", "xor"}
_OPS3 = {"mux"}


def _load(ref, nb: bool) -> jax.Array:
    """Read a tile, complementing in-register when its neg-mask bit is set."""
    x = ref[...]
    return ~x if nb else x


def _kernel1(op, neg, a_ref, o_ref):
    a = _load(a_ref, neg[0])
    o_ref[...] = ~a


def _kernel2(op, neg, a_ref, b_ref, o_ref):
    a, b = _load(a_ref, neg[0]), _load(b_ref, neg[1])
    if op == "and":
        o_ref[...] = a & b
    elif op == "nand":
        o_ref[...] = ~(a & b)
    elif op == "or":
        o_ref[...] = a | b
    elif op == "nor":
        o_ref[...] = ~(a | b)
    elif op == "xor":
        o_ref[...] = a ^ b


def _kernel3(op, neg, a_ref, b_ref, s_ref, o_ref):
    a, b = _load(a_ref, neg[0]), _load(b_ref, neg[1])
    s = _load(s_ref, neg[2])
    o_ref[...] = (a & s) | (b & ~s)  # fused scaled addition


@functools.partial(jax.jit, static_argnames=("op", "block_sub", "block_lanes",
                                             "interpret", "neg"))
def packed_logic(op: str, *args: jax.Array, block_sub: int = 32,
                 block_lanes: int = 1024, interpret: bool | None = None,
                 neg: tuple[bool, ...] = ()) -> jax.Array:
    """Apply a packed logic op over same-shape uint32 word tensors.

    ``neg[j]`` complements operand ``j`` inside the kernel before the op
    (``CompiledOp.neg``, the absorbed-lone-NOT mask); ``()`` means none.
    ``interpret`` resolves through ``common.resolve_interpret``.
    """
    shape = args[0].shape
    tiles = [lane_tiles(x) for x in args]
    lead, sub, lanes = tiles[0].shape
    sub_blk, lane_blk = min(block_sub, sub), min(block_lanes, lanes)
    grid = (lead, pl.cdiv(sub, sub_blk), pl.cdiv(lanes, lane_blk))
    spec = pl.BlockSpec((None, sub_blk, lane_blk), lambda g, i, j: (g, i, j))

    if op in _OPS1:
        kernel, n_in = _kernel1, 1
    elif op in _OPS2:
        kernel, n_in = _kernel2, 2
    elif op in _OPS3:
        kernel, n_in = _kernel3, 3
    else:
        raise ValueError(op)
    if len(args) != n_in:
        raise ValueError(f"{op} expects {n_in} operands")
    if neg and len(neg) != n_in:
        raise ValueError(f"{op} neg mask has {len(neg)} entries "
                         f"for {n_in} operands")
    full_neg = tuple(neg) if neg else (False,) * n_in

    out = pl.pallas_call(
        functools.partial(kernel, op, full_neg),
        grid=grid,
        in_specs=[spec] * n_in,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(tiles[0].shape, jnp.uint32),
        interpret=resolve_interpret(interpret),
        name="packed_logic",
    )(*tiles)
    return from_lane_tiles(out, shape)
