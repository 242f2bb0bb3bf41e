"""Dynamic bank serving: bucketed/padded BankPlans + BankServer.

Pins the tentpole guarantees of the serving layer:

  * padded/bucketed bank execution (``plan.compile_bank_template`` +
    ``executor.execute_bank`` with an active-slot mask) is **bit-identical**
    per bound slot to standalone ``execute`` — for random member subsets,
    batch shapes, both ``key_mode``s, and under bitflip injection;
  * ``BankServer`` results are bit-identical to per-request
    ``execute_value``, and its bucketing reuses templates (and jit traces)
    across request sets that fit the same bucket;
  * the plan/bank caches are LRU-bounded with evictions reported in
    ``cache_info()``;
  * the NOT-directed fusion passes (AND folding, lone-NOT absorption) reduce
    passes and stay bit-identical on the exp/Horner netlists.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import arch, circuits, executor
from repro.core.plan import (bucket_count, cache_info, compile_bank_template,
                             compile_plan, identity_plan, merged_pass_count,
                             set_cache_caps, template_members)
from repro.serve import BankServer, app_netlist, app_request, circuit_request

KEY = jax.random.key(11)
FLIP_KEY = jax.random.key(111)
BL = 256

# Shared structure pool: reusing these objects interns each to one plan.
MUL = circuits.sc_multiply()
SADD = circuits.sc_scaled_add()
ABS = circuits.sc_abs_sub()
SQRT = circuits.sc_sqrt()
EXP = circuits.sc_exp()
DIV = circuits.sc_scaled_div()

POOL = [
    (MUL, {"a": 0.3, "b": 0.7}),
    (SADD, {"a": 0.2, "b": 0.9}),
    (ABS, {"a": 0.4, "b": 0.1}),
    (SQRT, {"a": 0.5}),
    (EXP, {"a": 0.5}),
    (DIV, {"a": 0.4, "b": 0.2}),
]


def _requests(member_ids, batch=None):
    nets, values = [], []
    for m in member_ids:
        net, vals = POOL[m]
        nets.append(net)
        vals = {k: jnp.float32(v) for k, v in vals.items()}
        if batch:
            vals = {k: jnp.broadcast_to(v, batch) for k, v in vals.items()}
        values.append(vals)
    return nets, values


def _bind(template, plans):
    """Request -> slot binding over a template (first free slot per plan)."""
    from collections import defaultdict, deque
    free = defaultdict(deque)
    for s, m in enumerate(template.members):
        free[id(m)].append(s)
    return [free[id(p)].popleft() for p in plans]


def assert_padded_matches_loop(member_ids, batch=None, key_mode="batched",
                               bitflip_rate=0.0, bl=BL):
    nets, values = _requests(member_ids, batch)
    keys = jax.random.split(KEY, len(nets))
    fkeys = jax.random.split(FLIP_KEY, len(nets)) \
        if bitflip_rate > 0.0 else None
    fuse = bitflip_rate == 0.0
    plans = [compile_plan(n, fuse_mux=fuse or n.is_sequential) for n in nets]
    template = compile_bank_template(
        plans, n_slots=bucket_count(len(template_members(plans))))
    slots = _bind(template, plans)
    n = template.n_members
    values_seq = [{} for _ in range(n)]
    key_rows = [keys[0]] * n
    fk_rows = [fkeys[0] if fkeys is not None else keys[0]] * n
    active = [False] * n
    for r, s in enumerate(slots):
        values_seq[s] = values[r]
        key_rows[s] = keys[r]
        active[s] = True
        if fkeys is not None:
            fk_rows[s] = fkeys[r]
    outs = executor.execute_bank(
        template, values_seq, key_rows, bl, active=active,
        bitflip_rate=bitflip_rate,
        flip_keys=fk_rows if fkeys is not None else None, key_mode=key_mode)
    for r, s in enumerate(slots):
        ref = executor.execute(nets[r], values[r], keys[r], bl,
                               key_mode=key_mode, bitflip_rate=bitflip_rate,
                               flip_key=fkeys[r] if fkeys is not None
                               else None)
        assert set(outs[s]) == set(ref)
        for o in ref:
            assert outs[s][o].shape == ref[o].shape
            assert (outs[s][o] == ref[o]).all(), \
                f"member {r} ({nets[r].name}) output {o} diverges"
    for s in range(n):
        if s not in slots:
            assert outs[s] is None


# ------------------------------ padded execution ----------------------------------

@pytest.mark.parametrize("key_mode", ["batched", "legacy"])
def test_padded_bank_bit_identical(key_mode):
    assert_padded_matches_loop([0, 0, 0, 3, 5], key_mode=key_mode)


def test_padded_bank_bit_identical_with_batch():
    assert_padded_matches_loop([0, 1, 2, 4], batch=(5,))


@pytest.mark.parametrize("rate", [0.05, 0.2])
def test_padded_bank_bit_identical_under_bitflip(rate):
    assert_padded_matches_loop([0, 0, 3, 5], bitflip_rate=rate)


def test_active_all_true_normalizes_to_maskless():
    # A fully-bound template must share its jit signature with active=None.
    assert executor._normalize_active(None, 3) is None
    assert executor._normalize_active([True, True, True], 3) is None
    assert executor._normalize_active([True, False, True], 3) == \
        (True, False, True)
    with pytest.raises(ValueError, match="active"):
        executor._normalize_active([True], 3)


def test_execute_bank_rejects_reference_backend():
    template = compile_bank_template([compile_plan(MUL)])
    with pytest.raises(ValueError, match="reference"):
        executor.execute_bank(template, [{"a": jnp.float32(0.5),
                                          "b": jnp.float32(0.5)}],
                              KEY, BL, backend="reference")


# --------------------------------- templates --------------------------------------

def test_template_pads_counts_to_pow2_and_total_with_identity():
    p_mul, p_sqrt = compile_plan(MUL), compile_plan(SQRT)
    members = template_members([p_mul, p_mul, p_mul, p_sqrt], n_slots=8)
    assert members.count(p_mul) == 4          # 3 -> 4 (power of two)
    assert members.count(p_sqrt) == 1
    assert members.count(identity_plan()) == 3
    assert len(members) == 8


def test_template_is_canonical_across_arrival_order():
    p_mul, p_sqrt, p_div = (compile_plan(MUL), compile_plan(SQRT),
                            compile_plan(DIV))
    t1 = compile_bank_template([p_mul, p_sqrt, p_mul, p_div], n_slots=8)
    t2 = compile_bank_template([p_div, p_mul, p_mul, p_sqrt], n_slots=8)
    assert t1 is t2                           # same bucket -> same BankPlan
    # Counts that pad to the same power of two share the bucket: 3 and 4
    # muls both occupy a 4-slot structure group.
    t3 = compile_bank_template([p_mul] * 3 + [p_sqrt, p_div], n_slots=8)
    t4 = compile_bank_template([p_mul] * 4 + [p_sqrt, p_div], n_slots=8)
    assert t3 is t4


def test_template_bucket_counts():
    assert [bucket_count(n) for n in (1, 2, 3, 4, 5, 9)] == [1, 2, 4, 4, 8, 16]


def test_identity_plan_is_inert_singleton():
    ip = identity_plan()
    assert ip is identity_plan()
    assert ip.is_identity and ip.n_passes == 0 and not ip.outputs


def test_merged_pass_count_matches_bank():
    plans = [compile_plan(n) for n, _ in POOL]
    template = compile_bank_template(plans, n_slots=8)
    assert merged_pass_count(list(template.members)) == template.n_passes


# ------------------------------- arch accounting ----------------------------------

def test_evaluate_bank_plan_reports_padding_overhead():
    p_mul, p_exp = compile_plan(MUL), compile_plan(EXP)
    template = compile_bank_template([p_mul, p_mul, p_mul, p_exp], n_slots=8)
    # Bind only the three mul requests: exp's slot is padding this batch.
    active = [False] * template.n_members
    bound = 0
    for s, m in enumerate(template.members):
        if m is p_mul and bound < 3:
            active[s] = True
            bound += 1
    cost = arch.evaluate_bank_plan(template, arch.StochIMCConfig(),
                                   active=active)
    assert cost.active_members == 3
    assert cost.active_passes == merged_pass_count([p_mul])
    assert cost.padding_overhead_passes == \
        template.n_passes - cost.active_passes > 0
    assert 0.0 < cost.padding_overhead_frac < 1.0
    # Default accounting (no mask) excludes identity pads from "active".
    cost_all = arch.evaluate_bank_plan(template, arch.StochIMCConfig())
    assert cost_all.active_members == template.n_members - \
        template.n_identity_members
    assert cost_all.padding_overhead_passes == 0


# --------------------------------- BankServer -------------------------------------

def test_bank_server_bit_identical_to_per_request_execute():
    server = BankServer(max_slots=4, window_s=None)
    keys = jax.random.split(jax.random.key(3), 8)
    reqs = [circuit_request(MUL, {"a": jnp.float32(0.3),
                                  "b": jnp.float32(0.7)}, keys[0]),
            circuit_request(MUL, {"a": jnp.asarray([0.2, 0.8], jnp.float32),
                                  "b": jnp.full((2,), 0.5, jnp.float32)},
                            keys[1]),
            circuit_request(SQRT, {"a": jnp.float32(0.6)}, keys[2]),
            circuit_request(DIV, {"a": jnp.float32(0.4),
                                  "b": jnp.float32(0.4)}, keys[3])]
    results = server.serve(reqs)
    for r, req in enumerate(reqs):
        ref = executor.execute_value(req.net, req.values, req.key,
                                     req.bitstream_length)
        assert set(results[r]) == set(ref)
        for o in ref:
            np.testing.assert_array_equal(np.asarray(results[r][o]),
                                          np.asarray(ref[o]))


def test_bank_server_buckets_hit_across_shuffled_waves():
    server = BankServer(max_slots=8, window_s=None)
    keys = jax.random.split(jax.random.key(4), 16)

    def wave(order, key_off):
        reqs = []
        for j, (net, vals) in enumerate(order):
            vals = {k: jnp.float32(v) for k, v in vals.items()}
            reqs.append(circuit_request(net, vals, keys[key_off + j]))
        return server.serve(reqs)

    base = [POOL[0], POOL[0], POOL[0], POOL[3], POOL[5]]
    wave(base, 0)
    assert server.stats()["bucket_hit_rate"] == 0.0   # cold first batch
    wave(list(reversed(base)), 5)                     # same multiset, shuffled
    wave(base, 10)                                    # repeat traffic mix
    stats = server.stats()
    assert stats["n_batches"] == 3
    assert stats["bucket_hits"] == 2
    # mul pads 3 -> 4 and the 6-member template pads to 8 total slots.
    assert stats["padding_waste"] > 0.0
    assert stats["identity_slots"] > 0


def test_bank_server_mixed_bitstream_lengths_split_batches():
    server = BankServer(max_slots=8, window_s=None)
    keys = jax.random.split(jax.random.key(6), 4)
    reqs = [circuit_request(MUL, {"a": jnp.float32(0.4),
                                  "b": jnp.float32(0.6)}, keys[0], 256),
            circuit_request(MUL, {"a": jnp.float32(0.4),
                                  "b": jnp.float32(0.6)}, keys[1], 512)]
    res = server.serve(reqs)
    assert server.stats()["n_batches"] == 2           # bl is a static split
    for r, req in enumerate(reqs):
        ref = executor.execute_value(req.net, req.values, req.key,
                                     req.bitstream_length)
        for o in ref:
            np.testing.assert_array_equal(np.asarray(res[r][o]),
                                          np.asarray(ref[o]))


def test_bank_server_max_slots_triggers_flush_and_tickets_resolve():
    server = BankServer(max_slots=2, window_s=None)
    keys = jax.random.split(jax.random.key(7), 3)
    t1 = server.submit(circuit_request(MUL, {"a": jnp.float32(0.1),
                                             "b": jnp.float32(0.9)}, keys[0]))
    assert not t1.done()
    t2 = server.submit(circuit_request(MUL, {"a": jnp.float32(0.2),
                                             "b": jnp.float32(0.8)}, keys[1]))
    assert t1.done() and t2.done()                    # max_slots reached
    t3 = server.submit(circuit_request(SQRT, {"a": jnp.float32(0.3)},
                                       keys[2]))
    assert not t3.done()
    out = t3.result()                                 # result() flushes
    ref = executor.execute_value(SQRT, {"a": jnp.float32(0.3)}, keys[2], 256)
    np.testing.assert_array_equal(np.asarray(out["out"]),
                                  np.asarray(ref["out"]))
    assert t3.latency_s is not None and t3.latency_s >= 0.0


def test_bank_server_mixed_batch_shape_declarations_in_one_batch():
    # Regression: same-structure requests with and without a declared
    # batch_shape share a batch; the canonical-order sort must not compare
    # None against a tuple.
    server = BankServer(max_slots=4, window_s=None)
    keys = jax.random.split(jax.random.key(14), 2)
    reqs = [circuit_request(SQRT, {"a": jnp.float32(0.4)}, keys[0]),
            circuit_request(SQRT, {"a": jnp.full((3,), 0.6, jnp.float32)},
                            keys[1], batch_shape=(3,))]
    res = server.serve(reqs)
    for r, req in enumerate(reqs):
        ref = executor.execute_value(req.net, req.values, req.key, 256,
                                     batch_shape=req.batch_shape)
        for o in ref:
            np.testing.assert_array_equal(np.asarray(res[r][o]),
                                          np.asarray(ref[o]))


def test_bank_server_max_slots_flushes_only_the_filled_group():
    # Regression: one group reaching max_slots must not force other groups'
    # partial batches out early (they keep accumulating toward their own
    # triggers).
    server = BankServer(max_slots=2, window_s=None)
    keys = jax.random.split(jax.random.key(13), 3)
    t_slow = server.submit(circuit_request(MUL, {"a": jnp.float32(0.2),
                                                 "b": jnp.float32(0.4)},
                                           keys[0], 512))
    server.submit(circuit_request(MUL, {"a": jnp.float32(0.3),
                                        "b": jnp.float32(0.5)}, keys[1], 256))
    t_256b = server.submit(circuit_request(MUL, {"a": jnp.float32(0.6),
                                                 "b": jnp.float32(0.7)},
                                           keys[2], 256))
    assert t_256b.done()                      # bl=256 group hit max_slots
    assert not t_slow.done()                  # bl=512 group still queued
    ref = executor.execute_value(MUL, {"a": jnp.float32(0.2),
                                       "b": jnp.float32(0.4)}, keys[0], 512)
    np.testing.assert_array_equal(np.asarray(t_slow.result()["out"]),
                                  np.asarray(ref["out"]))


def test_bank_server_window_zero_flushes_on_submit():
    # window_s=0.0: a queued request never waits behind another submit; the
    # synchronous engine evaluates the window at submit time.
    server = BankServer(max_slots=8, window_s=0.0)
    key = jax.random.key(12)
    t = server.submit(circuit_request(MUL, {"a": jnp.float32(0.3),
                                            "b": jnp.float32(0.5)}, key))
    assert t.done()
    ref = executor.execute_value(MUL, {"a": jnp.float32(0.3),
                                       "b": jnp.float32(0.5)}, key, 256)
    np.testing.assert_array_equal(np.asarray(t.result()["out"]),
                                  np.asarray(ref["out"]))


def test_bank_server_bitflip_requests_thread_flip_keys():
    server = BankServer(max_slots=4, window_s=None)
    keys = jax.random.split(jax.random.key(8), 2)
    fks = jax.random.split(jax.random.key(9), 2)
    reqs = [circuit_request(MUL, {"a": jnp.float32(0.3),
                                  "b": jnp.float32(0.7)}, keys[i],
                            bitflip_rate=0.1, flip_key=fks[i])
            for i in range(2)]
    res = server.serve(reqs)
    for r, req in enumerate(reqs):
        ref = executor.execute_value(req.net, req.values, req.key, 256,
                                     bitflip_rate=0.1, flip_key=fks[r])
        for o in ref:
            np.testing.assert_array_equal(np.asarray(res[r][o]),
                                          np.asarray(ref[o]))
    with pytest.raises(ValueError, match="flip_key"):
        server.submit(circuit_request(MUL, {"a": jnp.float32(0.1),
                                            "b": jnp.float32(0.2)},
                                      keys[0], bitflip_rate=0.1))


def test_app_request_served_matches_appnet_stochastic():
    from repro.core import apps
    server = BankServer(max_slots=4, window_s=None)
    keys = jax.random.split(jax.random.key(10), 2)
    p = np.full((16, 6), 0.9)
    res = server.serve([app_request("ol", keys[0], 256, p=p),
                        app_request("ol", keys[1], 256, p=p * 0.8)])
    ref = apps.appnet_stochastic("ol", keys[0], bl=256,
                                 net=app_netlist("ol"), p=p)
    for o in ref:
        np.testing.assert_array_equal(np.asarray(res[0][o]),
                                      np.asarray(ref[o]))


# ----------------------------------- LRU caches -----------------------------------

def test_plan_cache_lru_bounded_with_evictions_reported():
    caps = set_cache_caps()
    before = cache_info()["plan_evictions"]
    try:
        set_cache_caps(plans=2)
        nets = [circuits.sc_exp(c=0.1 * (i + 1)) for i in range(5)]
        plans = [compile_plan(n) for n in nets]
        info = cache_info()
        assert info["plans"] <= 2
        assert info["plan_evictions"] >= before + 3
        # Live (memoized) plans still intern per netlist instance.
        assert compile_plan(nets[-1]) is plans[-1]
    finally:
        set_cache_caps(plans=caps["plans"], banks=caps["banks"])


def test_bank_cache_lru_bounded_with_evictions_reported():
    caps = set_cache_caps()
    before = cache_info()["bank_evictions"]
    try:
        set_cache_caps(banks=1)
        p = compile_plan(MUL)
        for n_slots in (2, 4, 8, 16):
            compile_bank_template([p], n_slots=n_slots)
        info = cache_info()
        assert info["banks"] <= 1
        assert info["bank_evictions"] >= before + 3
    finally:
        set_cache_caps(plans=caps["plans"], banks=caps["banks"])


def test_cache_info_reports_caps_and_eviction_counters():
    info = cache_info()
    for k in ("plans", "banks", "plan_cap", "bank_cap", "plan_evictions",
              "bank_evictions", "and_fused", "not_absorbed"):
        assert k in info


# ------------------------------ NOT-directed fusion -------------------------------

def test_and_folding_collapses_multiply_and_exp_ladder():
    p_mul = compile_plan(circuits.sc_multiply())
    assert p_mul.n_passes == 1 and p_mul.n_fused_and == 1
    assert p_mul.levels[0][0].op == "AND"
    # The exp Horner ladder: every NOT(NAND(A_k, C_k)) pair folds.
    p_exp = compile_plan(circuits.sc_exp())
    assert p_exp.n_fused_and == 4
    assert p_exp.n_passes < p_exp.n_gates - p_exp.n_fused_and


@pytest.mark.parametrize("c", [1.0, 0.8])
def test_fused_exp_horner_bit_identical(c):
    net = circuits.sc_exp(c)
    vals = {"a": jnp.float32(0.5)}
    ref = executor.execute(net, vals, KEY, 512, backend="reference")
    cmp = executor.execute(net, vals, KEY, 512)
    for o in ref:
        assert (ref[o] == cmp[o]).all()


def test_not_absorption_reduces_divider_passes_bit_identically():
    net = circuits.sc_scaled_div()
    plan = compile_plan(net)
    assert plan.n_not_absorbed >= 1
    assert plan.n_passes == 1                 # MUX fusion + NOT absorption
    vals = {"a": jnp.float32(0.4), "b": jnp.float32(0.4)}
    ref = executor.execute(net, vals, KEY, 1024, backend="reference")
    cmp = executor.execute(net, vals, KEY, 1024)
    for o in ref:
        assert (ref[o] == cmp[o]).all()


def test_not_absorption_keeps_observable_nots():
    from repro.core.gates import Netlist
    net = Netlist("obs_not")
    a = net.add_pi("A", value_key="a")
    b = net.add_pi("B", value_key="b")
    net.add_gate("NOT", [a], "na")
    net.add_gate("NAND", ["na", b], "out")
    net.set_outputs(["out", "na"])            # the NOT is observable
    plan = compile_plan(net)
    assert plan.n_not_absorbed == 0
    vals = {"a": jnp.float32(0.3), "b": jnp.float32(0.8)}
    ref = executor.execute(net, vals, KEY, 512, backend="reference")
    cmp = executor.execute(net, vals, KEY, 512)
    assert set(cmp) == {"out", "na"}
    for o in ref:
        assert (ref[o] == cmp[o]).all()


def test_fusion_disabled_without_fuse_mux():
    plan = compile_plan(circuits.sc_multiply(), fuse_mux=False)
    assert plan.n_fused_and == plan.n_not_absorbed == 0
    assert plan.n_passes == 2


# --------------------------------- property test ----------------------------------

member_sets = st.lists(st.integers(min_value=0, max_value=len(POOL) - 1),
                       min_size=1, max_size=6)
batches = st.sampled_from([None, (2,), (3,)])
key_modes = st.sampled_from(["batched", "legacy"])
rates = st.sampled_from([0.0, 0.1])


@settings(max_examples=20, deadline=None)
@given(member_sets, batches, key_modes, rates)
def test_property_padded_bank_bit_identical(members, batch, key_mode, rate):
    """Padded-bank execution == looped execute for random member subsets,
    batch shapes, both key modes, including bitflip injection."""
    assert_padded_matches_loop(members, batch=batch, key_mode=key_mode,
                               bitflip_rate=rate, bl=128)
