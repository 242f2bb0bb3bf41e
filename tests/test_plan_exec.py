"""Compiled execution plans are bit-identical to the reference interpreter.

Every backend claim of executor.py is pinned here with exact stream equality
(not value tolerance): combinational, sequential (Gaines-divider class),
bitflip-injected, and binary netlists; MUX fusion; plan/jit cache reuse; and
the Pallas-routed pass variant.
"""
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import apps, circuits, executor
from repro.core import plan as plan_mod
from repro.core.appnet import APP_NETLISTS
from repro.core.gates import Netlist
from repro.core.plan import (DEFAULT_PIPELINE, FUSED_MUX, PassPipeline,
                             compile_plan, lower_netlist)

KEY = jax.random.key(0)
FLIP_KEY = jax.random.key(99)
BL = 1024

SC_CASES = [
    (circuits.sc_multiply, {"a": 0.3, "b": 0.7}),
    (circuits.sc_scaled_add, {"a": 0.2, "b": 0.9}),
    (circuits.sc_scaled_add_var, {"a": 0.2, "b": 0.9, "s": 0.4}),
    (circuits.sc_abs_sub, {"a": 0.4, "b": 0.1}),
    (circuits.sc_sqrt, {"a": 0.5}),
    (circuits.sc_exp, {"a": 0.5}),
]


def assert_streams_equal(net, values, bl=BL, **kw):
    ref = executor.execute(net, values, KEY, bl, backend="reference", **kw)
    cmp = executor.execute(net, values, KEY, bl, backend="compiled", **kw)
    assert set(ref) == set(cmp)
    for o in ref:
        assert (ref[o] == cmp[o]).all(), f"{net.name}:{o} diverges"


# ------------------------------ combinational -------------------------------------

@pytest.mark.parametrize("builder,values", SC_CASES,
                         ids=[b.__name__ for b, _ in SC_CASES])
def test_combinational_bit_identical(builder, values):
    assert_streams_equal(builder(), {k: jnp.float32(v) for k, v in values.items()})


def test_combinational_batched_values_bit_identical():
    net = circuits.sc_multiply()
    a = jnp.asarray(np.linspace(0.1, 0.9, 8), jnp.float32)
    assert_streams_equal(net, {"a": a, "b": jnp.full((8,), 0.5, jnp.float32)})


def test_mux_tree_bit_identical_and_fused():
    net = Netlist("tree")
    leaves = [net.add_pi(f"L{i}", value_key=f"v{i}") for i in range(8)]
    root = circuits.sc_mux_tree(leaves, net)
    net.set_outputs([root])
    vals = {f"v{i}": jnp.float32(0.1 * (i + 1)) for i in range(8)}
    assert_streams_equal(net, vals)
    plan = compile_plan(net)
    assert plan.n_fused_mux == 7           # balanced tree over 8 leaves
    assert plan.n_passes < plan.n_gates


# -------------------------------- sequential --------------------------------------

def test_sequential_divider_bit_identical():
    net = circuits.sc_scaled_div()
    assert_streams_equal(net, {"a": jnp.float32(0.4), "b": jnp.float32(0.4)},
                         bl=2048)


def test_sequential_batched_bit_identical():
    net = circuits.sc_scaled_div()
    a = jnp.asarray(np.linspace(0.1, 0.6, 4), jnp.float32)
    assert_streams_equal(net, {"a": a, "b": jnp.full((4,), 0.3, jnp.float32)},
                         bl=512)


def test_sequential_inverting_output_bit_identical_and_correct():
    # Regression: an output driven by a NOT gate carries garbage in bits
    # 1..31 of the per-step values; both backends must mask before packing.
    net = Netlist("div_with_qbar_out")
    a = net.add_pi("A", value_key="a")
    b = net.add_pi("B", value_key="b")
    from repro.core.gates import PIKind
    q = net.add_pi("Q", kind=PIKind.STATE)
    qb = net.add_gate("NOT", [q], "Q_bar")
    bb = net.add_gate("NOT", [b], "B_bar")
    n1 = net.add_gate("NAND", [a, qb], "n1")
    n2 = net.add_gate("NAND", [bb, q], "n2")
    qn = net.add_gate("NAND", [n1, n2], "Q_next")
    qnb = net.add_gate("NOT", [qn], "Qn_bar")
    net.bind_state(q, qn, init=0.0)
    net.set_outputs([qn, qnb])
    vals = {"a": jnp.float32(0.4), "b": jnp.float32(0.5)}
    assert_streams_equal(net, vals, bl=2048)
    out = executor.execute_value(net, vals, jax.random.key(2), 16384)
    assert abs(float(out["Q_next"]) - 0.4 / 0.9) < 0.03
    assert abs(float(out["Qn_bar"]) - (1 - 0.4 / 0.9)) < 0.03


def test_sequential_value_converges():
    # The scan-over-words path reproduces the divider fixed point.
    out = executor.execute_value(circuits.sc_scaled_div(),
                                 {"a": jnp.float32(0.4), "b": jnp.float32(0.4)},
                                 jax.random.key(1), 16384, backend="compiled")
    assert abs(float(out["Q_next"]) - 0.5) < 0.03


# --------------------------------- bitflips ---------------------------------------

@pytest.mark.parametrize("rate", [0.05, 0.2])
def test_bitflip_combinational_bit_identical(rate):
    for builder, values in SC_CASES[:3]:
        assert_streams_equal(builder(),
                             {k: jnp.float32(v) for k, v in values.items()},
                             bitflip_rate=rate, flip_key=FLIP_KEY)


def test_bitflip_sequential_bit_identical():
    assert_streams_equal(circuits.sc_scaled_div(),
                         {"a": jnp.float32(0.4), "b": jnp.float32(0.2)},
                         bl=512, bitflip_rate=0.1, flip_key=FLIP_KEY)


def test_bitflip_uses_unfused_plan():
    net = circuits.sc_scaled_add()
    assert compile_plan(net, fuse_mux=True).n_fused_mux == 1
    assert compile_plan(net, fuse_mux=False).n_fused_mux == 0


# ---------------------------------- binary ----------------------------------------

@pytest.mark.parametrize("n_bits", [3, 8])
def test_binary_adder_bit_identical_and_correct(n_bits):
    rng = np.random.default_rng(n_bits)
    a = jnp.asarray(rng.integers(0, 1 << n_bits, 64), jnp.uint32)
    b = jnp.asarray(rng.integers(0, 1 << n_bits, 64), jnp.uint32)
    net = circuits.binary_ripple_carry_adder(n_bits)
    bits = circuits.rca_prepare_inputs(a, b, n_bits)
    ref = executor.execute_binary(net, bits, backend="reference")
    cmp = executor.execute_binary(net, bits, backend="compiled")
    for o in ref:
        assert (ref[o] == cmp[o]).all()
    dec = circuits.rca_decode_outputs(cmp, n_bits)
    assert (np.asarray(dec) == np.asarray(a) + np.asarray(b)).all()


def test_binary_missing_operand_raises():
    net = circuits.binary_ripple_carry_adder(2)
    with pytest.raises(KeyError):
        executor.execute_binary(net, {"A0": jnp.zeros((4,), jnp.uint32)},
                                backend="compiled")


# --------------------------------- appnets ----------------------------------------

def test_appnet_ol_bit_identical_and_level_batched():
    net = APP_NETLISTS["ol"]()
    vals = apps.appnet_inputs("ol", p=np.full((16, 6), 0.8))
    assert_streams_equal(net, vals, bl=256)
    plan = compile_plan(net)
    # 16 parallel pixel circuits collapse to one fused pass per level.
    assert plan.n_gates == 160 and plan.n_passes <= 10


def test_appnet_hdp_sequential_bit_identical():
    vals = {k: jnp.float32(0.5) for k in apps.HDP_KEYS}
    net = APP_NETLISTS["hdp"]()
    assert_streams_equal(net, apps.appnet_inputs("hdp", v=vals), bl=256)


def test_appnet_stochastic_tracks_exact_product():
    p = np.full((16, 6), 0.9)
    out = apps.appnet_stochastic("ol", jax.random.key(3), bl=2048, p=p)
    got = np.asarray(list(out.values())).mean()
    assert abs(got - 0.9 ** 6) < 0.05


# ------------------------------ plan properties -----------------------------------

def test_plan_cache_interns_equal_structures():
    p1 = compile_plan(circuits.sc_multiply())
    p2 = compile_plan(circuits.sc_multiply())
    assert p1 is p2


def test_mutating_compiled_netlist_recompiles():
    # Regression: the per-instance memo was keyed on PI/gate *counts*, so an
    # in-place gate replacement at equal count returned the stale plan.
    net = circuits.sc_multiply()            # NAND(A,B) -> NOT -> out = a*b
    p1 = compile_plan(net)
    net.replace_gate(0, gtype="NOR")        # same gate count, new structure
    p2 = compile_plan(net)
    assert p2 is not p1
    assert p2.levels[0][0].op == "NOR"
    # And the recompiled plan executes the *new* semantics:
    # out = NOT(NOR(a, b)) = a OR b.
    vals = {"a": jnp.float32(0.3), "b": jnp.float32(0.6)}
    out = executor.execute_value(net, vals, jax.random.key(0), 8192)
    expected = 0.3 + 0.6 - 0.3 * 0.6
    assert abs(float(out["out"]) - expected) < 0.03
    assert_streams_equal(net, vals)
    # The per-instance memo stays bounded across mutate/recompile cycles
    # (stale-version entries are evicted on the next compile).
    for gt in ("NAND", "NOR", "AND", "OR") * 2:
        net.replace_gate(0, gtype=gt)
        compile_plan(net)
    assert len(net._plan_memo) <= 2


def test_fusion_is_not_applied_to_observable_intermediates():
    # If a MUX intermediate is also a primary output it must stay
    # materialized — no fusion may swallow it.
    net = Netlist("observed_mux")
    a = net.add_pi("A", value_key="a")
    b = net.add_pi("B", value_key="b")
    s = net.add_pi("S", value_key="s")
    sb = net.add_gate("NOT", [s], "sb")
    n1 = net.add_gate("NAND", [a, s], "n1")
    n2 = net.add_gate("NAND", [b, sb], "n2")
    net.add_gate("NAND", [n1, n2], "out")
    net.set_outputs(["out", "n1"])
    plan = compile_plan(net)
    assert plan.n_fused_mux == 0
    vals = {"a": jnp.float32(0.3), "b": jnp.float32(0.6), "s": jnp.float32(0.5)}
    assert_streams_equal(net, vals)


def test_fused_plan_collapses_scaled_add_to_single_pass():
    plan = compile_plan(circuits.sc_scaled_add())
    assert plan.n_passes == 1
    assert plan.levels[0][0].op == FUSED_MUX


# --------------------------- pinned pipeline goldens ------------------------------
# tests/golden_digests.json was captured from the pre-refactor compiler: the
# staged PassPipeline must reproduce every stream bit-for-bit and every
# optimization counter exactly (drift here means the refactor changed
# semantics, not just structure).

_GOLD = json.loads((pathlib.Path(__file__).parent
                    / "golden_digests.json").read_text())
GOLD_KEY = jax.random.key(42)
GOLD_FLIP = jax.random.key(7)
GOLD_BL = _GOLD["bitstream_length"]

GOLD_VALUES = {
    "sc_multiply": {"a": 0.3, "b": 0.7},
    "sc_scaled_add": {"a": 0.2, "b": 0.9},
    "sc_scaled_add_var": {"a": 0.2, "b": 0.9, "s": 0.4},
    "sc_abs_sub": {"a": 0.4, "b": 0.1},
    "sc_sqrt": {"a": 0.5},
    "sc_exp": {"a": 0.5},
    "sc_scaled_div": {"a": 0.4, "b": 0.4},
}


def _digest(streams, order) -> str:
    # Hash output streams by declared-output POSITION, not node name: node
    # names embed a process-global counter, so they depend on how many
    # netlists were built earlier in the process (goldens must not).
    h = hashlib.sha256()
    for i, name in enumerate(order):
        arr = np.asarray(streams[name])
        h.update(str(i).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()[:16]


def _golden_case(name):
    """(netlist, values, bitstream_length) for a golden-digest case name."""
    if name == "sc_multiply_batched":
        a = jnp.asarray(np.linspace(0.1, 0.9, 8), jnp.float32)
        return (circuits.sc_multiply(),
                {"a": a, "b": jnp.full((8,), 0.5, jnp.float32)}, GOLD_BL)
    if name.startswith("appnet_"):
        app = name.removeprefix("appnet_")
        kw = ({"p": np.full((16, 6), 0.8)} if app == "ol" else
              {"v": {k: jnp.float32(0.5) for k in apps.HDP_KEYS}})
        return APP_NETLISTS[app](), apps.appnet_inputs(app, **kw), 256
    return (getattr(circuits, name)(),
            {k: jnp.float32(v) for k, v in GOLD_VALUES[name].items()}, GOLD_BL)


@pytest.mark.parametrize("case", sorted(_GOLD["digests"]))
def test_pipeline_matches_pre_refactor_golden_digest(case):
    name, key_mode, variant = case.split("/")
    net, vals, bl = _golden_case(name)
    kw = dict(bitflip_rate=0.05, flip_key=GOLD_FLIP) \
        if variant == "bitflip" else {}
    streams = executor.execute(net, vals, GOLD_KEY, bl,
                               key_mode=key_mode, **kw)
    assert _digest(streams, net.outputs) == _GOLD["digests"][case], case


def test_plan_counters_match_goldens():
    for name, want in _GOLD["plan_counters"].items():
        net, _, _ = _golden_case(name)
        p = compile_plan(net)
        got = {k: getattr(p, k) for k in want}
        assert got == want, name


_DRIFT_KEYS = ("buff_elided", "cse_elided", "mux_fused", "xor_fused",
               "and_fused", "not_absorbed")


@pytest.mark.parametrize("app", sorted(_GOLD["app_pass_counters"]))
def test_app_pass_counters_no_drift(app):
    # CI drift check (see pyproject/README): the cache_info() optimization
    # counters for each Table-3 app bank are pinned — a pipeline-stage change
    # that alters how many nodes fuse/elide must update the goldens on
    # purpose, not silently.
    want = _GOLD["app_pass_counters"][app]
    plan_mod.clear_cache()
    before = {k: plan_mod.cache_info().get(k, 0) for k in _DRIFT_KEYS}
    bank = plan_mod.compile_bank_plan(apps.cost_stage_netlists(app))
    after = plan_mod.cache_info()
    got = {k: after.get(k, 0) - before[k] for k in _DRIFT_KEYS}
    got["merged_passes"] = bank.n_passes
    got["looped_passes"] = bank.n_passes_looped
    # Liveness pin: the merged bank's peak simultaneously-live streams
    # (scratch slots).  Drift means the liveness stage's allocation — and so
    # megakernel scratch sizing and subarray occupancy — changed.
    got["max_live"] = max(g.max_live
                          for g in (bank.comb, bank.seq) if g is not None)
    assert got == want, app


def test_clear_cache_invalidates_per_netlist_memo():
    # Regression (cache staleness): clear_cache() empties the interning
    # caches, but the per-netlist _plan_memo used to keep pointing at the
    # old plan object — a post-clear compile returned a plan no longer in
    # any cache, silently defeating the clear.
    net = circuits.sc_multiply()
    p1 = compile_plan(net)
    plan_mod.clear_cache()
    p2 = compile_plan(net)
    assert p2 is not p1
    # Epoch-stale memo entries are pruned, not accumulated.
    for _ in range(5):
        plan_mod.clear_cache()
        compile_plan(net)
        compile_plan(net, fuse_mux=False)
    assert len(net._plan_memo) <= 2


def test_every_plan_carries_a_schedule():
    for name in ("sc_multiply", "sc_scaled_div", "appnet_ol"):
        net, _, _ = _golden_case(name)
        p = compile_plan(net)
        assert p.schedule is not None
        assert p.schedule.logic_cycles >= p.n_passes


@settings(max_examples=20, deadline=None)
@given(idx=st.integers(0, len(GOLD_VALUES) - 1),
       fuse=st.booleans(),
       key_mode=st.sampled_from(("batched", "legacy")),
       frac=st.floats(0.05, 0.95))
def test_property_pipeline_bit_identical(idx, fuse, key_mode, frac):
    # Property (random netlist x pipeline config x fuse_mux): the staged
    # pipeline's compiled output is bit-identical to the reference
    # interpreter, and rebuilding the PassPipeline from its own stages
    # lowers to the identical pass program.
    name = sorted(GOLD_VALUES)[idx]
    net = getattr(circuits, name)()
    vals = {k: jnp.float32(round(min(max(v * frac * 2.0, 0.05), 0.95), 3))
            for k, v in GOLD_VALUES[name].items()}
    # fuse=False exercises the unfused plan via the bitflip path (the only
    # execute() entry that selects it).
    kw = {} if fuse else dict(bitflip_rate=0.05, flip_key=GOLD_FLIP)
    ref = executor.execute(net, vals, GOLD_KEY, 256, backend="reference",
                           key_mode=key_mode, **kw)
    cmp = executor.execute(net, vals, GOLD_KEY, 256, backend="compiled",
                           key_mode=key_mode, **kw)
    assert set(ref) == set(cmp)
    for o in ref:
        assert (ref[o] == cmp[o]).all(), f"{name}:{o}"
    p_default = lower_netlist(net, fuse_mux=fuse)
    p_rebuilt = lower_netlist(
        net, fuse_mux=fuse,
        pipeline=PassPipeline(stages=DEFAULT_PIPELINE.stages))
    assert p_rebuilt.levels == p_default.levels
    assert p_rebuilt.aliases == p_default.aliases
    assert p_rebuilt.stream_table == p_default.stream_table
    assert p_rebuilt.schedule.logic_cycles == p_default.schedule.logic_cycles


# ---------------------------------- pallas ----------------------------------------

@pytest.mark.pallas
def test_pallas_backend_bit_identical():
    for builder, values in (SC_CASES[0], SC_CASES[3]):
        net = builder()
        vals = {k: jnp.float32(v) for k, v in values.items()}
        ref = executor.execute(net, vals, KEY, 256, backend="reference")
        pal = executor.execute(net, vals, KEY, 256, backend="compiled_pallas")
        for o in ref:
            assert (ref[o] == pal[o]).all()


# ------------------- word-tiled streaming & megakernel goldens --------------------
# The chunked-jnp scan path and the whole-plan Pallas megakernel must both
# reproduce the pre-refactor golden digests bit for bit, in both key modes —
# streaming/fusing the execution may never change a single output bit.

_CLEAN_CASES = sorted(c for c in _GOLD["digests"] if c.endswith("/fused"))


def _is_sequential_case(name: str) -> bool:
    net, _, _ = _golden_case(name)
    return net.is_sequential


@pytest.mark.parametrize(
    "case", [c for c in _CLEAN_CASES if not _is_sequential_case(c.split("/")[0])])
def test_chunked_streaming_matches_golden_digest(case):
    name, key_mode, _ = case.split("/")
    net, vals, bl = _golden_case(name)
    w = bl // 32
    for chunk in (1, w // 2):
        streams = executor.run(executor.ExecRequest(
            net, vals, GOLD_KEY, executor.ExecOptions(
                bitstream_length=bl, key_mode=key_mode, word_chunk=chunk)))
        assert _digest(streams, net.outputs) == _GOLD["digests"][case], \
            (case, chunk)


@pytest.mark.pallas
@pytest.mark.parametrize("case", _CLEAN_CASES)
def test_megakernel_matches_golden_digest(case):
    name, key_mode, _ = case.split("/")
    net, vals, bl = _golden_case(name)
    streams = executor.run(executor.ExecRequest(
        net, vals, GOLD_KEY, executor.ExecOptions(
            bitstream_length=bl, key_mode=key_mode,
            backend="compiled_megakernel", interpret=True)))
    assert _digest(streams, net.outputs) == _GOLD["digests"][case], case


@pytest.mark.pallas
def test_chunked_megakernel_composes():
    # word_chunk + megakernel: the scan body runs the fused kernel per chunk.
    net, vals, bl = _golden_case("sc_exp")
    case = "sc_exp/batched/fused"
    streams = executor.run(executor.ExecRequest(
        net, vals, GOLD_KEY, executor.ExecOptions(
            bitstream_length=bl, word_chunk=4,
            backend="compiled_megakernel", interpret=True)))
    assert _digest(streams, net.outputs) == _GOLD["digests"][case]


def _state_only_oscillator() -> Netlist:
    from repro.core.gates import PIKind
    n = Netlist("osc")
    q = n.add_pi("Q", kind=PIKind.STATE)
    qn = n.add_gate("NOT", [q], "Qn")
    n.bind_state(q, qn, init=0.0)
    n.set_outputs([qn])
    return n


def test_sequential_zero_stream_pi_respects_batch_shape():
    # Regression: a sequential plan with zero stream PIs used to ignore
    # batch_shape= entirely — the scan fell back to scalar state, returning
    # (W,) outputs for a (5,)-batched request.
    net = _state_only_oscillator()
    out = executor.execute(net, {}, KEY, 256, batch_shape=(5,))
    assert out["Qn"].shape == (5, 8)
    base = executor.execute(net, {}, KEY, 256)
    assert base["Qn"].shape == (8,)
    for i in range(5):
        assert (out["Qn"][i] == base["Qn"]).all()


def test_sequential_zero_stream_pi_word_chunk_raises():
    # The streaming executor cannot re-chunk a state recurrence; asking for
    # word_chunk on such a plan must fail loudly, not silently ignore it.
    net = _state_only_oscillator()
    with pytest.raises(ValueError, match="word_chunk"):
        executor.run(executor.ExecRequest(
            net, {}, KEY, executor.ExecOptions(
                bitstream_length=256, batch_shape=(5,), word_chunk=2)))


def test_word_chunk_rejects_injection_and_bad_sizes():
    net, vals, bl = _golden_case("sc_multiply")
    with pytest.raises(ValueError, match="fault injection"):
        executor.run(executor.ExecRequest(
            net, vals, GOLD_KEY, executor.ExecOptions(
                bitstream_length=bl, word_chunk=4,
                bitflip_rate=0.05, flip_key=GOLD_FLIP)))
    with pytest.raises(ValueError, match="divide"):
        executor.run(executor.ExecRequest(
            net, vals, GOLD_KEY, executor.ExecOptions(
                bitstream_length=bl, word_chunk=5)))
    with pytest.raises(ValueError, match="single-plan"):
        executor.run([executor.ExecRequest(
            net, vals, GOLD_KEY, executor.ExecOptions(
                bitstream_length=bl, word_chunk=4))] * 2)


def test_liveness_annotation_invariants():
    # Every compiled plan carries a valid register-allocation: slots stay
    # below max_live, a slot is never reassigned while its node is live, and
    # outputs/state drivers are never freed.
    for name in ("sc_exp", "sc_sqrt", "appnet_ol", "appnet_hdp"):
        net, _, _ = _golden_case(name)
        p = compile_plan(net)
        assert 0 < p.max_live <= p.naive_live
        alias = dict(p.aliases)
        protected = {alias.get(nm, nm)
                     for nm in (*p.outputs, *p.state_drivers)}
        slot_of = {pi.name: s for pi, s in zip(p.pis, p.pi_slots) if s >= 0}
        live = dict(slot_of)
        for level in p.levels:
            for cop in level:
                assert len(cop.slots) == len(cop.outputs)
                for nm, s in zip(cop.outputs, cop.slots):
                    assert 0 <= s < p.max_live
                    # Slot must be free: no OTHER live node holds it.
                    holders = [n for n, ls in live.items() if ls == s]
                    assert holders in ([], [nm]), (name, nm, holders)
                    live[nm] = s
                for nm in cop.free_after:
                    assert nm not in protected, (name, nm)
                    live.pop(nm, None)
        assert max(live.values(), default=-1) < p.max_live


def test_free_after_releases_dead_intermediates():
    # The per-pass executor drops dead nodes from env as it goes: after a
    # full run only live-at-exit names (plus aliases) remain.
    net, vals, bl = _golden_case("sc_exp")
    p = compile_plan(net)
    from repro.core.streams import _gen_pi_streams
    from repro.kernels.netlist_exec import run_combinational
    env = dict(_gen_pi_streams(p.pis, {k: jnp.float32(v) for k, v in
                                       vals.items()}, GOLD_KEY, bl))
    n_pis = len(env)
    run_combinational(p, env)
    # env holds at most the liveness bound plus re-exposed aliases — not one
    # entry per node (sc_exp has 13 gates + PIs).
    assert len(env) <= p.max_live + len(p.aliases)
    assert n_pis + p.n_gates > p.max_live  # the bound actually binds
    for o in p.outputs:
        assert o in env
