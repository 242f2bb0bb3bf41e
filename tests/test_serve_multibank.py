"""Multi-bank async serving + the unified ExecRequest/ExecOptions API.

Pins for the device-sharded BankServer and the executor.run() redesign:

  * every legacy ``execute*`` entry point is a bit-identical thin shim over
    ``executor.run(ExecRequest(...))`` — pinned for all six spellings, both
    ``key_mode``s, with bitflip injection and declared batch shapes;
  * the deprecated plural-kwarg spellings (``keys=`` / ``batch_shapes=``)
    raise ``DeprecationWarning`` but still compute the same bits;
  * serving sharded across devices is bit-identical to single-device
    serving and to standalone ``execute_value`` (the ISSUE acceptance
    anchor), for every placement policy;
  * continuous batching: a request arriving while a compatible batch is
    staged-but-held joins that batch in place (no extra dispatch);
  * a failed dispatch propagates its exception to *every* ticket of the
    batch and leaves the server serviceable;
  * ``Ticket.result(timeout=...)`` bounds the wait and keeps the ticket
    retryable;
  * per-device stats account every dispatched batch/request.

Multi-device cases skip on single-device hosts; CI forces
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so they run there.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import circuits, executor
from repro.core.executor import ExecOptions, ExecRequest
from repro.core.plan import compile_plan, compile_bank_template, \
    template_members
from repro.serve import BankServer, app_request, circuit_request

KEY = jax.random.key(21)
FLIP = jax.random.key(2121)
BL = 256

MUL = circuits.sc_multiply()
SADD = circuits.sc_scaled_add()
SQRT = circuits.sc_sqrt()
EXP = circuits.sc_exp()

POOL = {
    "mul": (MUL, {"a": 0.3, "b": 0.7}),
    "sadd": (SADD, {"a": 0.2, "b": 0.9}),
    "sqrt": (SQRT, {"a": 0.5}),
    "exp": (EXP, {"a": 0.4}),
}

KEY_MODES = ["batched", "legacy"]


def tree_eq(a, b) -> bool:
    if a is None or b is None:
        return a is b
    if sorted(a) != sorted(b):
        return False
    return all(bool(jnp.array_equal(a[k], b[k])) for k in a)


# ----------------------------- run()/shim parity ----------------------------------


@pytest.mark.parametrize("key_mode", KEY_MODES)
def test_execute_shim_matches_run(key_mode):
    vals = {"a": 0.3, "b": 0.7}
    shim = executor.execute(MUL, vals, KEY, BL, key_mode=key_mode)
    new = executor.run(ExecRequest(MUL, vals, KEY, ExecOptions(
        key_mode=key_mode, bitstream_length=BL)))
    assert tree_eq(shim, new)


@pytest.mark.parametrize("key_mode", KEY_MODES)
def test_execute_value_shim_matches_run(key_mode):
    vals = {"a": 0.2, "b": 0.9}
    shim = executor.execute_value(SADD, vals, KEY, BL, key_mode=key_mode)
    new = executor.run(ExecRequest(SADD, vals, KEY, ExecOptions(
        key_mode=key_mode, bitstream_length=BL, decode=True)))
    assert tree_eq(shim, new)


def test_execute_with_bitflip_and_batch_shape_matches_run():
    vals = {"a": np.full((4,), 0.5, np.float32)}
    shim = executor.execute(SQRT, vals, KEY, BL, bitflip_rate=0.05,
                            flip_key=FLIP)
    new = executor.run(ExecRequest(SQRT, vals, KEY, ExecOptions(
        bitstream_length=BL, bitflip_rate=0.05, flip_key=FLIP)))
    assert tree_eq(shim, new)
    # All-const batch declaration flows through options.batch_shape.
    shim = executor.execute(MUL, {"a": 0.3, "b": 0.7}, KEY, BL,
                            batch_shape=(3,))
    new = executor.run(ExecRequest(MUL, {"a": 0.3, "b": 0.7}, KEY,
                                   ExecOptions(bitstream_length=BL,
                                               batch_shape=(3,))))
    assert tree_eq(shim, new)


def test_execute_binary_shim_matches_run():
    bits = {"A": jnp.asarray([0x0F0F0F0F], jnp.uint32),
            "B": jnp.asarray([0x00FF00FF], jnp.uint32)}
    shim = executor.execute_binary(MUL, bits)
    new = executor.run(ExecRequest(MUL, dict(bits),
                                   options=ExecOptions(binary=True)))
    assert tree_eq(shim, new)


@pytest.mark.parametrize("key_mode", KEY_MODES)
def test_execute_many_shims_match_run(key_mode):
    names = ["mul", "sadd", "sqrt"]
    nets = [POOL[n][0] for n in names]
    values = [dict(POOL[n][1]) for n in names]
    keys = jax.random.split(KEY, len(nets))
    shared = ExecOptions(key_mode=key_mode, bitstream_length=BL)
    reqs = [ExecRequest(nets[i], values[i], keys[i], shared)
            for i in range(len(nets))]
    legacy = executor.execute_many(nets, values, keys, BL, key_mode=key_mode)
    assert all(tree_eq(a, b) for a, b in zip(legacy, executor.run(reqs)))
    legacy = executor.execute_value_many(nets, values, keys, BL,
                                         key_mode=key_mode)
    reqs = [ExecRequest(nets[i], values[i], keys[i],
                        ExecOptions(key_mode=key_mode, bitstream_length=BL,
                                    decode=True))
            for i in range(len(nets))]
    assert all(tree_eq(a, b) for a, b in zip(legacy, executor.run(reqs)))


def test_plural_kwargs_deprecated_but_identical():
    nets = [MUL, SADD]
    values = [dict(POOL["mul"][1]), dict(POOL["sadd"][1])]
    keys = jax.random.split(KEY, 2)
    want = executor.execute_many(nets, values, keys, BL)
    with pytest.warns(DeprecationWarning, match="keys=.*deprecated"):
        got = executor.execute_many(nets, values, keys=keys,
                                    bitstream_length=BL)
    assert all(tree_eq(a, b) for a, b in zip(want, got))
    with pytest.warns(DeprecationWarning, match="batch_shapes=.*deprecated"):
        got = executor.execute_value_many(nets, values, keys, BL,
                                          batch_shapes=[None, None])
    want = executor.execute_value_many(nets, values, keys, BL)
    assert all(tree_eq(a, b) for a, b in zip(want, got))


@pytest.mark.parametrize("key_mode", KEY_MODES)
def test_run_template_matches_execute_bank_and_standalone(key_mode):
    names = ["mul", "sadd", "mul"]
    plans = [compile_plan(POOL[n][0]) for n in names]
    bank = compile_bank_template(plans)
    members = template_members(plans)
    keys = jax.random.split(KEY, len(names))
    # Bind each request to the first free slot holding its plan.
    slot_reqs = [None] * bank.n_members
    taken = set()
    for i, n in enumerate(names):
        s = next(j for j, p in enumerate(members)
                 if p is plans[i] and j not in taken)
        taken.add(s)
        slot_reqs[s] = ExecRequest(POOL[n][0], dict(POOL[n][1]), keys[i],
                                   ExecOptions(key_mode=key_mode,
                                               bitstream_length=BL,
                                               decode=True))
    outs = executor.run(slot_reqs, template=bank)
    for s, req in enumerate(slot_reqs):
        if req is None:
            assert outs[s] is None
            continue
        ref = executor.execute_value(req.net, req.values, req.key, BL,
                                     key_mode=key_mode)
        assert tree_eq(outs[s], ref)


# ----------------------------- sharded serving ------------------------------------


def _mixed_requests(n, bl=BL, seed=3):
    keys = jax.random.split(jax.random.key(seed), n)
    names = sorted(POOL)
    return [circuit_request(POOL[names[i % len(names)]][0],
                            dict(POOL[names[i % len(names)]][1]),
                            keys[i], bl)
            for i in range(n)]


@pytest.mark.parametrize("key_mode", KEY_MODES)
def test_sharded_matches_single_device_and_standalone(key_mode):
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")
    reqs = _mixed_requests(12)
    sharded = BankServer(max_slots=4, devices=jax.devices(),
                         placement="round_robin", max_inflight=2,
                         key_mode=key_mode)
    single = BankServer(max_slots=4, devices=[jax.devices()[0]],
                        key_mode=key_mode)
    outs_s = sharded.serve(reqs)
    outs_1 = single.serve(reqs)
    for r, a, b in zip(reqs, outs_s, outs_1):
        assert tree_eq(a, b)
        assert tree_eq(a, executor.execute_value(r.net, r.values, r.key, BL,
                                                 key_mode=key_mode))
    # round_robin over >= 2 devices must actually have used more than one.
    st_ = sharded.stats()
    assert sum(1 for d in st_["devices"] if d["n_batches"]) >= 2


@pytest.mark.parametrize("placement", ["affinity", "least_loaded"])
def test_placements_stay_bit_identical(placement):
    reqs = _mixed_requests(8)
    server = BankServer(max_slots=4, devices=jax.devices(),
                        placement=placement, max_inflight=2)
    for r, out in zip(reqs, server.serve(reqs)):
        ref = executor.execute_value(r.net, r.values, r.key, BL)
        assert tree_eq(out, ref)


def test_per_device_stats_account_everything():
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    reqs = _mixed_requests(16)
    server = BankServer(max_slots=4, devices=jax.devices(),
                        placement="round_robin", max_inflight=1)
    server.serve(reqs)
    st_ = server.stats()
    assert st_["n_devices"] == jax.device_count()
    assert len(st_["devices"]) == jax.device_count()
    assert sum(d["n_batches"] for d in st_["devices"]) == st_["n_batches"]
    assert sum(d["n_requests"] for d in st_["devices"]) == len(reqs)
    assert "joined_requests" in st_


# ----------------------------- continuous batching --------------------------------


def test_late_request_joins_staged_batch():
    server = BankServer(max_slots=4)
    server.hold()
    # 3x mul + 1x sqrt hits max_slots: the batch forms and stages (held, so
    # it does not dispatch).  pad_counts rounds the mul run to 4 slots, so
    # the staged batch holds exactly one free mul slot for a late joiner.
    keys = jax.random.split(jax.random.key(31), 5)
    reqs = [circuit_request(MUL, {"a": 0.1 * (i + 1), "b": 0.5}, keys[i], BL)
            for i in range(3)]
    reqs.append(circuit_request(SQRT, {"a": 0.6}, keys[3], BL))
    tickets = [server.submit(r) for r in reqs]
    assert server.stats()["n_batches"] == 0          # staged, not dispatched
    late = circuit_request(MUL, {"a": 0.45, "b": 0.55}, keys[4], BL)
    t_late = server.submit(late)                     # joins the held batch
    server.release()
    outs = [t.result() for t in tickets]
    assert server.stats()["n_batches"] == 1          # one dispatch total
    assert server.stats()["joined_requests"] >= 1
    for r, out in zip(reqs, outs):
        assert tree_eq(out, executor.execute_value(r.net, r.values, r.key,
                                                   BL))
    assert tree_eq(t_late.result(),
                   executor.execute_value(late.net, late.values, late.key,
                                          BL))


# ----------------------------- failure handling -----------------------------------


def test_failure_propagates_to_every_ticket_and_server_survives():
    server = BankServer(max_slots=2)
    good = circuit_request(MUL, {"a": 0.3, "b": 0.7}, jax.random.key(4), BL)
    bad = circuit_request(MUL, {"a": 0.3}, jax.random.key(5), BL)  # missing b
    t1 = server.submit(good)
    t2 = server.submit(bad)                 # max_slots reached: one batch
    with pytest.raises(Exception):
        t2.result()
    with pytest.raises(Exception):
        t1.result()                                  # same batch -> same error
    # The server stays serviceable after a failed batch.
    ok = _mixed_requests(1, seed=7)[0]
    out = server.serve([ok])[0]
    assert tree_eq(out, executor.execute_value(ok.net, ok.values, ok.key, BL))


def test_result_timeout_keeps_ticket_retryable():
    server = BankServer(max_slots=2, window_s=0.0)
    # Big enough that the async dispatch cannot have finished synchronously.
    req = circuit_request(EXP, {"a": np.full((512,), 0.4, np.float32)},
                          jax.random.key(9), 4096)
    ticket = server.submit(req)
    server.flush()
    try:
        out = ticket.result(timeout=0.0)
    except TimeoutError:
        out = ticket.result()                        # retry without bound
    ref = executor.execute_value(req.net, req.values, req.key, 4096)
    assert tree_eq(out, ref)


# ----------------------------- property sweep -------------------------------------


@given(data=st.data())
@settings(max_examples=8, deadline=None)
def test_property_serving_bit_identity_across_devices(data):
    n_dev = data.draw(st.integers(min_value=1,
                                  max_value=jax.device_count()),
                      label="n_devices")
    placement = data.draw(st.sampled_from(["affinity", "round_robin",
                                           "least_loaded"]),
                          label="placement")
    names = data.draw(st.lists(st.sampled_from(sorted(POOL)), min_size=1,
                               max_size=6), label="mix")
    max_inflight = data.draw(st.integers(min_value=0, max_value=2),
                             label="max_inflight")
    keys = jax.random.split(jax.random.key(17), len(names))
    reqs = [circuit_request(POOL[n][0], dict(POOL[n][1]), keys[i], 64)
            for i, n in enumerate(names)]
    server = BankServer(max_slots=4, devices=jax.devices()[:n_dev],
                        placement=placement, max_inflight=max_inflight)
    for r, out in zip(reqs, server.serve(reqs)):
        ref = executor.execute_value(r.net, r.values, r.key, 64)
        assert tree_eq(out, ref)


def test_app_request_builders_return_canonical_execrequests():
    a = np.linspace(0.1, 0.9, 81)
    req = app_request("lit", KEY, BL, a=a)
    assert isinstance(req, ExecRequest)
    assert isinstance(req.options, ExecOptions)
    assert req.options.decode is False               # server decodes via opts
    out = executor.run(ExecRequest(req.net, req.values, req.key, ExecOptions(
        bitstream_length=BL, decode=True)))
    ref = executor.execute_value(req.net, req.values, req.key, BL)
    assert tree_eq(out, ref)
