"""Reliability semantics of the serving engine (PR-8).

Pins for the fault-tolerant BankServer:

  * **backpressure** — ``max_queue`` bounds admission; ``"reject"`` fails
    the new ticket with ``RequestShed``, ``"shed_oldest"`` evicts the
    oldest queued request; both count in stats;
  * **deadlines** — ``deadline_ms`` fails the ticket with the *permanent*
    ``DeadlineExceeded`` (deliberately NOT a ``TimeoutError`` subclass:
    ``Ticket.result(timeout=...)`` raises ``TimeoutError`` and stays
    retryable);
  * **retry** — failed batches re-queue with backoff up to ``max_retries``;
    a successful retry is bit-identical to a clean single-shot run; past
    the budget the ORIGINAL exception (with a ``[BankServer]`` note)
    fails the ticket;
  * **quarantine** — consecutive device failures trip the breaker,
    in-flight work re-dispatches to healthy devices without consuming
    retry budget, the last healthy device is never quarantined, and a
    healed device is re-admitted after its probe passes;
  * **chaos** — a rotating-kill trace loses zero tickets and stays
    bit-identical;
  * **shutdown** — ``close()``/``__exit__`` drains every outstanding
    ticket (even while a device is quarantined); ``close(drain=False)``
    fails undispatched tickets with ``ServerClosed``.

Multi-device cases skip on single-device hosts; CI forces
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so they run there.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import circuits, dispatch, executor
from repro.serve import (BankServer, DeadlineExceeded, RequestShed,
                         ServerClosed, circuit_request)

BL = 128
MUL = circuits.sc_multiply()

needs_multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 jax devices (CI sets "
           "--xla_force_host_platform_device_count=4)")


def req(i: int, **kw):
    return circuit_request(MUL, {"a": 0.1 + 0.05 * (i % 10), "b": 0.6},
                           jax.random.key(i), BL, **kw)


def ref(r):
    return executor.run(r, options=dataclasses.replace(r.options,
                                                       decode=True))


def tree_eq(a, b) -> bool:
    return sorted(a) == sorted(b) and \
        all(bool(jnp.array_equal(a[k], b[k])) for k in a)


class FailFirstN:
    """Injector failing the first ``n`` batch launches (probes pass)."""

    def __init__(self, n: int):
        self.remaining = n
        self.kills = 0

    def __call__(self, device, batch):
        if batch is None:
            return
        if self.remaining > 0:
            self.remaining -= 1
            self.kills += 1
            raise RuntimeError("injected launch failure")


class FailDeviceNth:
    """Fail the ``nth`` launch (0-based) seen on one specific device."""

    def __init__(self, device, nth: int = 1):
        self.device = device
        self.nth = nth
        self.seen = 0

    def __call__(self, device, batch):
        if batch is None or device != self.device:
            return
        i = self.seen
        self.seen += 1
        if i == self.nth:
            raise RuntimeError("injected device failure")


class FailDeviceWhile:
    """Fail every launch on ``device`` while ``self.down`` is True."""

    def __init__(self, device):
        self.device = device
        self.down = True

    def __call__(self, device, batch):
        if batch is not None and self.down and device == self.device:
            raise RuntimeError("device is down")


# ------------------------------- backpressure ---------------------------------


def test_reject_overload_fails_new_ticket():
    with BankServer(max_slots=8, max_queue=2, overload="reject") as srv:
        t0, t1 = srv.submit(req(0)), srv.submit(req(1))
        t2 = srv.submit(req(2))
        with pytest.raises(RequestShed):
            t2.result()
        assert srv.stats()["shed_requests"] == 1
        srv.flush()
        assert tree_eq(t0.result(), ref(req(0)))
        assert tree_eq(t1.result(), ref(req(1)))


def test_shed_oldest_evicts_queue_head():
    with BankServer(max_slots=8, max_queue=2,
                    overload="shed_oldest") as srv:
        t0 = srv.submit(req(0))
        srv.submit(req(1))
        t2 = srv.submit(req(2))          # evicts t0, admits t2
        with pytest.raises(RequestShed):
            t0.result()
        srv.flush()
        assert tree_eq(t2.result(), ref(req(2)))
        assert srv.stats()["shed_requests"] == 1


# --------------------------------- deadlines ----------------------------------


def test_deadline_exceeded_is_permanent_and_typed():
    with BankServer(max_slots=8) as srv:     # held: batch never forms
        t = srv.submit(req(0, deadline_ms=5.0))
        time.sleep(0.02)
        with pytest.raises(DeadlineExceeded):
            t.result()
        # Permanent: a second wait re-raises instead of retrying.
        with pytest.raises(DeadlineExceeded):
            t.result()
        assert srv.stats()["deadline_exceeded"] == 1
    assert not issubclass(DeadlineExceeded, TimeoutError)


def test_result_timeout_stays_retryable():
    # result() drives the engine, so a queued request simply executes; the
    # observable wait is a retry backoff window.  Fail the first launch
    # with a long backoff: the bounded wait raises TimeoutError, the
    # unbounded one rides out the backoff and returns clean bits.
    with BankServer(max_slots=1, max_retries=1, retry_backoff_s=0.2,
                    quarantine_after=100,
                    fault_injector=FailFirstN(1)) as srv:
        t = srv.submit(req(0))
        with pytest.raises(TimeoutError):
            t.result(timeout=0.01)
        assert not t.done()                  # retryable: ticket still live
        assert tree_eq(t.result(), ref(req(0)))


def test_generous_deadline_is_met():
    with BankServer(max_slots=1) as srv:
        t = srv.submit(req(0, deadline_ms=60_000.0))
        assert tree_eq(t.result(), ref(req(0)))
        assert srv.stats()["deadline_exceeded"] == 0


# ----------------------------------- retry ------------------------------------


def test_retry_after_failures_is_bit_identical():
    inj = FailFirstN(2)
    with BankServer(max_slots=1, max_retries=3, retry_backoff_s=0.001,
                    quarantine_after=100, fault_injector=inj) as srv:
        t = srv.submit(req(0))
        assert tree_eq(t.result(), ref(req(0)))
        assert inj.kills == 2
        assert srv.stats()["retries"] == 2


def test_retry_budget_exhausted_raises_original_with_note():
    class Boom(ValueError):
        pass

    def always_fail(device, batch):
        if batch is not None:
            raise Boom("boom")

    with BankServer(max_slots=1, max_retries=1, retry_backoff_s=0.001,
                    quarantine_after=100,
                    fault_injector=always_fail) as srv:
        t = srv.submit(req(0))
        with pytest.raises(Boom, match="boom") as exc_info:
            t.result()
        notes = getattr(exc_info.value, "__notes__", [])
        assert any("[BankServer]" in n for n in notes)
        assert len(notes) == 1               # noted once, not per retry
        assert srv.stats()["retries"] == 1


def test_no_retry_budget_fails_fast():
    inj = FailFirstN(1)
    with BankServer(max_slots=1, quarantine_after=100,
                    fault_injector=inj) as srv:
        t = srv.submit(req(0))
        with pytest.raises(RuntimeError, match="injected"):
            t.result()
        assert srv.stats()["retries"] == 0


def test_program_error_fails_ticket_without_retry_or_device_charge():
    # A batch whose program cannot be built — here a missing PI value, a
    # trace-time KeyError, as a kernel the chip's compiler refuses would be
    # at compile time — fails its tickets with that very error.  Retrying
    # would rebuild the same program, and the device did nothing wrong.
    bad = circuit_request(MUL, {"a": 0.3}, jax.random.key(5), BL)
    with BankServer(max_slots=1, max_retries=3, quarantine_after=1) as srv:
        t = srv.submit(bad)
        with pytest.raises(KeyError) as exc_info:
            t.result()
        assert any("[BankServer]" in n for n in exc_info.value.__notes__)
        st_ = srv.stats()
        assert st_["failed_requests"] == 1
        assert st_["retries"] == st_["quarantines"] == 0
        assert not any(srv._consec_failures.values())
        # The server stays serviceable.
        assert tree_eq(srv.submit(req(1)).result(), ref(req(1)))


class FlakyDispatch:
    """Stand-in for the jitted bank program whose *dispatch* raises a device
    error — out of HBM, as a chip under memory pressure reports it — on
    ``device`` (None: any) ``failures`` times; compiling it never fails."""

    def __init__(self, fn, device=None, failures: int = 1):
        self.fn, self.device, self.failures = fn, device, failures

    def trace(self, *args, **kw):
        return self.fn.trace(*args, **kw)

    def __call__(self, *args, **kw):
        keys = args[2]
        if self.failures and (self.device is None or
                              self.device in keys.devices()):
            self.failures -= 1
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: out of memory allocating the batch")
        return self.fn(*args, **kw)


@pytest.mark.parametrize("max_retries", [0, 1])
def test_dispatch_device_error_is_charged_to_device(monkeypatch, max_retries):
    # An error raised while the batch is enqueued, with a program that
    # compiles fine, is the device's: it is retried and counts towards the
    # device's quarantine.
    monkeypatch.setattr(dispatch, "_execute_bank",
                        FlakyDispatch(dispatch._execute_bank))
    d0 = jax.devices()[0]
    with BankServer(max_slots=1, max_retries=max_retries,
                    retry_backoff_s=0.001, quarantine_after=100) as srv:
        t = srv.submit(req(0))
        if max_retries:
            assert tree_eq(t.result(), ref(req(0)))
        else:
            with pytest.raises(jax.errors.JaxRuntimeError,
                               match="RESOURCE_EXHAUSTED"):
                t.result()
            assert srv._consec_failures[d0] == 1
        st_ = srv.stats()
        assert st_["retries"] == max_retries
        assert st_["failed_requests"] == 1 - max_retries


@needs_multidevice
def test_dispatch_device_error_moves_work_to_healthy_device(monkeypatch):
    devices = jax.devices()
    victim = devices[1]
    monkeypatch.setattr(dispatch, "_execute_bank", FlakyDispatch(
        dispatch._execute_bank, device=victim, failures=10 ** 6))
    with BankServer(max_slots=1, devices=devices, placement="round_robin",
                    max_retries=2, retry_backoff_s=0.001,
                    quarantine_after=1, quarantine_s=60.0) as srv:
        reqs = [req(i) for i in range(2 * len(devices))]
        tickets = [srv.submit(r) for r in reqs]
        srv.flush()
        for r, t in zip(reqs, tickets):
            assert tree_eq(t.result(), ref(r))
        st_ = srv.stats()
        assert st_["failed_requests"] == 0 and st_["retries"] >= 1
        assert [d["device"] for d in st_["devices"] if d["quarantined"]] \
            == [str(victim)]


@given(st.integers(min_value=1, max_value=3),
       st.sampled_from(["batched", "legacy"]),
       st.sampled_from(["affinity", "round_robin", "least_loaded"]),
       st.integers(min_value=1, max_value=max(1, jax.device_count())),
       st.sampled_from([1, 2, 100]))
@settings(max_examples=10, deadline=None)
def test_property_faulted_serving_bit_identical(n_failures, key_mode,
                                                placement, ndev, qafter):
    """Retries AND quarantine re-dispatch reproduce clean single-shot bits
    across key_modes, placements and device counts.  Low ``qafter`` with
    several devices trips the breaker (re-dispatch path); ``qafter=100``
    absorbs every failure through retries alone."""
    devices = jax.devices()[:ndev]
    with BankServer(max_slots=2, devices=devices, max_inflight=2,
                    placement=placement, key_mode=key_mode,
                    max_retries=3, retry_backoff_s=0.001,
                    quarantine_after=qafter, quarantine_s=0.005,
                    fault_injector=FailFirstN(n_failures)) as srv:
        reqs = [req(i) for i in range(6)]
        tickets = [srv.submit(r) for r in reqs]
        srv.flush()
        for r, t in zip(reqs, tickets):
            clean = executor.run(r, options=dataclasses.replace(
                r.options, decode=True, key_mode=key_mode))
            assert tree_eq(t.result(timeout=60.0), clean)


# --------------------------------- quarantine ---------------------------------


@needs_multidevice
def test_quarantine_redispatches_inflight_work():
    devices = jax.devices()
    inj = FailDeviceNth(devices[0], nth=1)
    with BankServer(max_slots=1, devices=devices, max_inflight=4,
                    placement="round_robin", max_retries=1,
                    retry_backoff_s=0.001, quarantine_after=1,
                    quarantine_s=30.0, fault_injector=inj) as srv:
        # hold() stages everything so flush launches the batches
        # back-to-back: the first launch on the victim device is still in
        # flight (not yet reaped) when its second launch is killed.
        srv.hold()
        reqs = [req(i) for i in range(2 * len(devices))]
        tickets = [srv.submit(r) for r in reqs]
        srv.flush()
        for r, t in zip(reqs, tickets):
            assert tree_eq(t.result(), ref(r))
        st_ = srv.stats()
        assert st_["quarantines"] == 1
        quarantined = [d for d in st_["devices"] if d["quarantined"]]
        assert len(quarantined) == 1
        # The batch in flight on the killed device was moved, not retried
        # (re-dispatch consumes no retry budget); only the killed launch
        # itself spent one retry.
        assert st_["redispatched_requests"] >= 1
        assert st_["retries"] <= 1


def test_last_healthy_device_never_quarantined():
    d0 = jax.devices()[0]
    inj = FailFirstN(2)
    with BankServer(max_slots=1, devices=[d0], max_retries=3,
                    retry_backoff_s=0.001, quarantine_after=1,
                    fault_injector=inj) as srv:
        t = srv.submit(req(0))
        assert tree_eq(t.result(), ref(req(0)))
        assert srv.stats()["quarantines"] == 0


@needs_multidevice
def test_quarantined_device_readmitted_after_heal():
    devices = jax.devices()
    inj = FailDeviceWhile(devices[0])
    with BankServer(max_slots=1, devices=devices, max_inflight=2,
                    placement="round_robin", max_retries=3,
                    retry_backoff_s=0.001, quarantine_after=2,
                    quarantine_s=0.005, fault_injector=inj) as srv:
        tickets = [srv.submit(req(i)) for i in range(2 * len(devices))]
        srv.flush()
        [t.result() for t in tickets]
        assert srv.stats()["quarantines"] >= 1
        inj.down = False                     # the device comes back
        deadline = time.monotonic() + 5.0
        while any(d["quarantined"] for d in srv.stats()["devices"]):
            srv.flush()
            if time.monotonic() > deadline:
                pytest.fail("healed device was never re-admitted")
            time.sleep(0.005)
        # And it serves again: round-robin will reach it within a few
        # batches once healthy.
        tickets = [srv.submit(req(100 + i)) for i in range(2 * len(devices))]
        srv.flush()
        for i, t in enumerate(tickets):
            assert tree_eq(t.result(), ref(req(100 + i)))


# ----------------------------------- chaos ------------------------------------


@needs_multidevice
def test_chaos_trace_loses_zero_tickets():
    devices = jax.devices()

    class RotatingKiller:
        def __init__(self, period=4):
            self.period = period
            self.launches = 0
            self.kills = 0

        def __call__(self, device, batch):
            if batch is None:
                return
            i = self.launches
            self.launches += 1
            victim = (i // self.period) % len(devices)
            if device == devices[victim]:
                self.kills += 1
                raise RuntimeError("chaos kill")

    inj = RotatingKiller()
    with BankServer(max_slots=4, devices=devices, max_inflight=2,
                    placement="round_robin", max_retries=3,
                    retry_backoff_s=0.001, quarantine_after=2,
                    quarantine_s=0.005, fault_injector=inj) as srv:
        reqs = [req(i) for i in range(32)]
        tickets = [srv.submit(r) for r in reqs]
        srv.flush()
        for r, t in zip(reqs, tickets):
            assert tree_eq(t.result(timeout=60.0), ref(r))
    assert inj.kills > 0


# ---------------------------------- shutdown ----------------------------------


def test_close_drains_outstanding_tickets():
    srv = BankServer(max_slots=8)            # held: nothing dispatches
    reqs = [req(i) for i in range(3)]
    tickets = [srv.submit(r) for r in reqs]
    srv.close()                              # drain=True default
    for r, t in zip(reqs, tickets):
        assert tree_eq(t.result(), ref(r))
    with pytest.raises(ServerClosed):
        srv.submit(req(9))
    srv.close()                              # idempotent


def test_close_without_drain_fails_queued_tickets():
    srv = BankServer(max_slots=8)
    t = srv.submit(req(0))
    srv.close(drain=False)
    with pytest.raises(ServerClosed):
        t.result()


def test_context_exit_drains_under_retry_load():
    inj = FailFirstN(2)
    with BankServer(max_slots=1, max_retries=3, retry_backoff_s=0.001,
                    quarantine_after=100, fault_injector=inj) as srv:
        reqs = [req(i) for i in range(3)]
        tickets = [srv.submit(r) for r in reqs]
        # exit drains: no explicit flush/result before close
    for r, t in zip(reqs, tickets):
        assert tree_eq(t.result(), ref(r))


@needs_multidevice
def test_close_while_device_quarantined_resolves_all():
    devices = jax.devices()
    inj = FailDeviceWhile(devices[0])
    srv = BankServer(max_slots=1, devices=devices, max_inflight=2,
                     placement="round_robin", max_retries=3,
                     retry_backoff_s=0.001, quarantine_after=1,
                     quarantine_s=60.0, fault_injector=inj)
    reqs = [req(i) for i in range(2 * len(devices))]
    tickets = [srv.submit(r) for r in reqs]
    srv.close()                              # drain with dev0 quarantined
    for r, t in zip(reqs, tickets):
        assert tree_eq(t.result(), ref(r))


def test_failed_batch_leaves_server_serviceable():
    inj = FailFirstN(1)
    with BankServer(max_slots=1, quarantine_after=100,
                    fault_injector=inj) as srv:
        t0 = srv.submit(req(0))
        with pytest.raises(RuntimeError):
            t0.result()
        t1 = srv.submit(req(1))              # server still works
        assert tree_eq(t1.result(), ref(req(1)))
