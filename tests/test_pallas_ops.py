"""Bit-identity of every Pallas logic kernel against the jnp gate algebra.

Parametrized over every op ``packed_logic`` implements (including the fused
4-gate MUX), every per-input complement mask (the in-kernel ``neg`` folding
of absorbed lone NOTs), and odd non-tile-aligned shapes — the kernels must
agree with ``core.bitstream``'s packed boolean algebra on every word, in
interpret mode (CI) and compiled alike.  Also pins the whole-plan megakernel
unit behavior: engagement, scratch reuse, and its documented fallbacks.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitstream as bs
from repro.core import circuits, executor, obs
from repro.core.plan import compile_plan
from repro.core.streams import _gen_pi_streams
from repro.kernels.common import InterpretModeWarning
from repro.kernels.netlist_exec import run_combinational
from repro.kernels.packed_logic import packed_logic
from repro.kernels.plan_megakernel import combinational_megakernel

pytestmark = pytest.mark.pallas

#: op name -> (arity, jnp reference over packed words)
_OPS = {
    "not": (1, bs.not_),
    "and": (2, bs.and_),
    "nand": (2, bs.nand),
    "or": (2, bs.or_),
    "nor": (2, bs.nor),
    "xor": (2, bs.xor),
    "mux": (3, lambda a, b, s: bs.mux(a, b, s)),
}

# (40, 3) and (2, 300, 32) have fewer words than rows: the kernels view them
# transposed (``common.lane_tiles``), which must change no bit.
_SHAPES = [(8, 128), (5, 7), (17, 129), (1, 1), (3, 300), (40, 3),
           (2, 300, 32)]


def _words(i, shape):
    return jax.random.bits(jax.random.key(i), shape, dtype=jnp.uint32)


@pytest.mark.parametrize("op", sorted(_OPS))
@pytest.mark.parametrize("shape", _SHAPES, ids=str)
def test_packed_logic_all_ops_all_shapes(op, shape):
    n_in, ref = _OPS[op]
    args = [_words(i, shape) for i in range(n_in)]
    got = packed_logic(op, *args, interpret=True)
    assert (got == ref(*args)).all(), (op, shape)


@pytest.mark.parametrize("op", sorted(_OPS))
def test_packed_logic_neg_masks_fold_in_kernel(op):
    # Every complement mask equals pre-complementing outside the kernel.
    n_in, ref = _OPS[op]
    args = [_words(10 + i, (5, 70)) for i in range(n_in)]
    for neg in itertools.product((False, True), repeat=n_in):
        got = packed_logic(op, *args, neg=neg, interpret=True)
        want = ref(*[~x if nb else x for x, nb in zip(args, neg)])
        assert (got == want).all(), (op, neg)


def test_packed_logic_off_chip_interprets_with_a_warning():
    # Left to choose its mode on a non-TPU backend, a kernel says it is
    # interpreting instead of passing silently for a compiled run.
    a, b = _words(20, (6, 9)), _words(21, (6, 9))
    with pytest.warns(InterpretModeWarning, match="interpret mode"):
        got = packed_logic("xor", a, b)
    assert (got == bs.xor(a, b)).all()


def test_packed_logic_validates_arity_and_neg():
    a, b = _words(0, (4, 4)), _words(1, (4, 4))
    with pytest.raises(ValueError):
        packed_logic("and", a, interpret=True)
    with pytest.raises(ValueError):
        packed_logic("and", a, b, neg=(True,), interpret=True)
    with pytest.raises(ValueError):
        packed_logic("frob", a, b, interpret=True)


# ------------------------------ whole-plan megakernel ------------------------------

def _plan_env(net, vals, bl=1024, shape=None):
    plan = compile_plan(net)
    streams = _gen_pi_streams(
        plan.pis, {k: jnp.float32(v) for k, v in vals.items()},
        jax.random.key(5), bl, batch_shape=shape)
    return plan, streams


@pytest.mark.parametrize("builder,vals", [
    (circuits.sc_multiply, {"a": 0.3, "b": 0.7}),
    (circuits.sc_scaled_add, {"a": 0.2, "b": 0.9}),
    (circuits.sc_abs_sub, {"a": 0.4, "b": 0.1}),
    (circuits.sc_sqrt, {"a": 0.5}),
    (circuits.sc_exp, {"a": 0.5}),
], ids=lambda x: getattr(x, "__name__", ""))
def test_megakernel_engages_and_matches_per_pass(builder, vals):
    plan, streams = _plan_env(builder(), vals, shape=(3,))
    ref_env = dict(streams)
    run_combinational(plan, ref_env)
    got = combinational_megakernel(plan, dict(streams), interpret=True)
    assert got is not None, "megakernel unexpectedly fell back"
    for o in plan.outputs:
        assert (got[o] == ref_env[o]).all(), o


def test_megakernel_scratch_pool_smaller_than_node_count():
    # sc_exp reuses slots: the VMEM pool is sized by liveness, not node count.
    plan = compile_plan(circuits.sc_exp())
    assert 0 < plan.max_live < plan.naive_live


def test_megakernel_falls_back_on_heterogeneous_pi_shapes():
    plan, streams = _plan_env(circuits.sc_multiply(), {"a": 0.3, "b": 0.7})
    streams = dict(streams)
    k = next(iter(streams))
    streams[k] = jnp.broadcast_to(streams[k], (2,) + streams[k].shape)
    before = _declines()
    assert combinational_megakernel(plan, streams, interpret=True) is None
    assert _declines() == before + 1


def _declines() -> int:
    return obs.REGISTRY.snapshot()["counters"].get("megakernel.declined", 0)


def test_mixed_shape_bank_counts_megakernel_decline():
    # A merged bank whose members carry different batch shapes cannot lower
    # into one kernel: the per-pass fallback still gives each member its
    # standalone bits, and the decline is counted, not silent.  (The odd
    # batch shape and BL keep this bank's jit trace private to this test.)
    net = circuits.sc_multiply()
    opts = executor.ExecOptions(backend="compiled_megakernel",
                                bitstream_length=96, interpret=True)
    reqs = [executor.ExecRequest(
                net, {"a": np.full((13,), 0.3, np.float32),
                      "b": np.full((13,), 0.7, np.float32)},
                jax.random.key(1), opts),
            executor.ExecRequest(net, {"a": 0.5, "b": 0.25},
                                 jax.random.key(2), opts)]
    before = _declines()
    merged = executor.run(reqs)
    assert _declines() > before
    for req, got in zip(reqs, merged):
        alone = executor.run(executor.ExecRequest(
            net, req.values, req.key,
            executor.ExecOptions(bitstream_length=96)))
        assert (got["out"] == alone["out"]).all()


def test_megakernel_rejects_fault_injection():
    net = circuits.sc_multiply()
    plan = compile_plan(net, fuse_mux=False)
    streams = _gen_pi_streams(
        plan.pis, {"a": jnp.float32(0.3), "b": jnp.float32(0.7)},
        jax.random.key(5), 1024)
    with pytest.raises(ValueError, match="megakernel"):
        run_combinational(plan, dict(streams),
                          gate_fkeys=jax.random.split(jax.random.key(0),
                                                      plan.n_gates),
                          bitflip_rate=0.1, megakernel=True)
