"""Multi-bank async serving: device-sharded, pipelined bank dispatch.

The bank compiler (core/plan.py) and executor serve a *fixed, ahead-of-time*
member list: every distinct request multiset costs a fresh BankPlan merge and
a fresh jit trace.  Real traffic — the ROADMAP's "heavy heterogeneous
traffic" north star, and the regime the memory-level-parallelism literature
targets — changes its member set every arrival, so a naive execute_many
server recompiles constantly and the accelerator starves.  The paper's
headline speedup additionally rests on *bank-level* parallelism: independent
subarrays computing concurrently.  ``BankServer`` models both axes:

  * **admission queue** — ``submit()`` enqueues a request and returns a
    ``Ticket``; batches form when ``max_slots`` requests of one execution
    group (same bitstream length / bitflip rate) are waiting, when the oldest
    waiting request exceeds the batching window, or on explicit ``flush()``
    / ``Ticket.result()`` (the engine is synchronous: time-based triggers are
    evaluated at submit/result boundaries, not by a background thread).
  * **bucketed, padded bank templates** — each batch maps to the canonical
    template of its member multiset (structures in deterministic order,
    per-structure slot counts padded to powers of two, identity members
    topping up the total).  Requests bind to slots (stable order: plan
    serial, then value shapes) and unbound slots are masked out, so any
    request set that fits a bucket reuses ONE BankPlan and ONE jit program.
  * **continuous batching** — a formed batch is *staged* before dispatch;
    requests arriving while it waits bind into its free (padding) slots
    instead of seeding a second batch (``stats()["joined_requests"]``).
  * **device sharding + async dispatch** — staged batches launch onto the
    least-loaded / round-robin / bank-affine JAX device (one bank per
    device, ``executor.run(..., device=...)``) and the server does NOT block
    on results: JAX async dispatch keeps up to ``max_inflight`` batches per
    device in flight while admission continues.  Tickets resolve to async
    arrays at dispatch; ``Ticket.result()`` waits (with optional timeout)
    and surfaces any execution failure on every ticket of the batch.
  * **per-request key threading** — every request carries its own PRNG key
    (and flip key under fault injection) and the executor draws slot streams
    exactly as standalone ``execute`` would: results are **bit-identical**
    per request to an unbatched run with the same key and ``key_mode``,
    regardless of device, bucket, or slot (pinned by tests/test_serve.py and
    tests/test_serve_multibank.py).

Reliability (fault-tolerant serving):

  * **bounded admission** — ``max_queue`` caps the waiting queue; overload
    either rejects the *new* request or sheds the *oldest* queued one
    (``overload="reject" | "shed_oldest"``), failing its ticket with a
    typed :class:`RequestShed` — no unbounded memory growth behind a
    stalled device.
  * **deadlines** — ``ExecOptions.deadline_ms`` bounds a request's total
    wall time (queue + retries + device); a passed deadline fails the
    ticket with :class:`DeadlineExceeded` (permanent — distinct from the
    retryable ``TimeoutError`` of ``Ticket.result(timeout=)``).
  * **bounded retry** — ``max_retries`` re-admits a failed batch's requests
    with exponential backoff (``retry_backoff_s * 2**attempt``); the
    request (and its keys) is unchanged, so a successful retry is
    **bit-identical** to a clean first-shot run.
  * **per-device circuit breaker** — ``quarantine_after`` consecutive batch
    failures on one device quarantine it for ``quarantine_s`` (doubling on
    repeated failure); its in-flight batches re-dispatch to healthy devices
    (without consuming retry budget), a health probe re-admits it, and the
    last healthy device is never quarantined.
  * **shutdown** — ``close()`` / context manager: drain mode resolves every
    queued/in-flight ticket (retries included); non-drain fails undispatched
    tickets with :class:`ServerClosed` and finalizes in-flight work.  The
    engine is synchronous (no threads), so close can never leak one.
  * **chaos hook** — ``fault_injector`` is called before every batch launch
    (and during health probes); raising simulates a device failure —
    the harness ``benchmarks/fault_campaign.py`` drives device kills
    through it.

``stats()`` reports serving health: bucket hit rate (how warm the
template/jit caches run), padding waste, join count, p50/p99 request
latency, throughput, per-device batch/request counts, and the reliability
counters (``shed_requests`` / ``retries`` / ``quarantines`` /
``redispatched_requests`` / ``deadline_exceeded``).
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, defaultdict, deque
from typing import Any

import jax
import numpy as np

from ..core import executor, obs
from ..core.arch import _plan_schedule_cycles
from ..core.dispatch import _check_fault_args
from ..core.executor import ExecOptions, ExecRequest
from ..core.faults import injecting, normalize_fault_model
from ..core.gates import Netlist
from ..core.plan import compile_bank_members, compile_plan, template_members


class DeadlineExceeded(Exception):
    """The request's ``deadline_ms`` passed before its result was delivered.

    Permanent: the ticket is failed and will not be retried.  Deliberately
    NOT a ``TimeoutError`` subclass — ``Ticket.result(timeout=)`` raises
    ``TimeoutError`` for a *retryable* bounded wait, while a deadline is a
    property of the request itself.
    """


class RequestShed(Exception):
    """The request was shed by admission backpressure (queue full)."""


class ServerClosed(Exception):
    """The server was closed before this request could be served."""


def _layout_sig_of(req: ExecRequest) -> tuple:
    """Batching-layout signature: PI names + shapes + declared batch shape.

    Requests with equal signatures occupy interchangeable bank slots, so
    the server sorts on this to canonicalize batch layouts (template-cache
    hits) and to match continuous-batching joins."""
    vs = tuple(sorted((k, tuple(v.shape) if hasattr(v, "shape")
                       else tuple(jax.numpy.shape(v)))
                      for k, v in req.values.items()))
    # Encode "no declared batch shape" as a comparable value: signatures
    # are sort keys, and None does not order against tuples.
    if req.batch_shape is None:
        return ((False, ()), vs)
    return ((True, tuple(req.batch_shape)), vs)


class SCRequest(ExecRequest):
    """One admitted stochastic-computation request.

    A thin subclass of :class:`repro.core.executor.ExecRequest` keeping the
    historical flat constructor: per-request execution parameters are folded
    into ``ExecOptions`` under the hood.  ``net`` is the circuit
    (structure-equal netlists intern to one compiled plan — reuse built
    netlist objects across requests to keep the plan memo warm, e.g. via
    ``repro.serve.apps``); ``values`` its PI values; ``key`` the request's
    own PRNG key (the bit-identity anchor).  ``batch_shape`` declares the
    stream batch shape when values alone cannot (all-const PIs).
    ``bitflip_rate``/``flip_key`` inject per-request transient faults;
    ``fault_model`` is the full STT-MRAM fault description
    (:class:`repro.core.faults.FaultModel` — subsumes ``bitflip_rate``).
    ``deadline_ms`` bounds the request's total wall time in the server
    (queue + retries + device); past it the ticket fails with
    :class:`DeadlineExceeded`.

    Values are canonicalized to *host* float32 at admission: a request is
    dispatched exactly once but its leaves are touched on every hot-path
    pass (signature, bind, bank call), so paying the dtype conversion here
    — once, at construction — keeps the dispatch loop cheap.  Host scalars
    are what ``execute_bank`` packs into one vector per slot at the jit
    boundary; jax-array values pass through untouched (forcing them to
    host would block on the device).
    """

    def __init__(self, net: Netlist, values: dict[str, Any], key: Any,
                 bitstream_length: int = 256,
                 batch_shape: "tuple[int, ...] | None" = None,
                 bitflip_rate: float = 0.0, flip_key: Any = None,
                 fault_model=None, deadline_ms: "float | None" = None,
                 options: "ExecOptions | None" = None):
        if options is None:
            options = ExecOptions(
                bitstream_length=bitstream_length,
                batch_shape=(tuple(batch_shape)
                             if batch_shape is not None else None),
                bitflip_rate=float(bitflip_rate), flip_key=flip_key,
                fault_model=fault_model, deadline_ms=deadline_ms)
        values = {k: v if isinstance(v, jax.Array)
                  else np.asarray(v, np.float32)
                  for k, v in values.items()}
        super().__init__(net=net, values=values, key=key, options=options)
        self._layout_sig = _layout_sig_of(self)


class Ticket:
    """Completion handle for a submitted request.

    ``done()`` turns True once the request's batch has been *dispatched*
    (results are then async jax arrays, possibly still computing) or failed.
    ``result()`` forces the wait and raises the batch's exception, if any.
    A retried request's ticket transiently drops back to not-done while it
    re-queues; ``result()`` drives the server until it settles.
    """

    __slots__ = ("_server", "_result", "_error", "_batch", "_done",
                 "submitted_at", "latency_s", "deadline_at")

    def __init__(self, server: "BankServer"):
        self._server = server
        self._result = None
        self._error: "BaseException | None" = None
        self._batch: "_Batch | None" = None
        self._done = False
        self.submitted_at = time.perf_counter()
        self.latency_s: float | None = None
        self.deadline_at: "float | None" = None

    def done(self) -> bool:
        return self._done

    def result(self, timeout: "float | None" = None):
        """The request's output dict; drives the server until it settles.

        ``timeout`` (seconds) bounds this *call*: raises ``TimeoutError``
        if the result has not landed in time (retryable — the ticket stays
        valid, call ``result()`` again).  The request's own ``deadline_ms``
        instead fails the ticket *permanently* with
        :class:`DeadlineExceeded`.  If the batch failed (after exhausting
        any retry budget), the original execution exception re-raises on
        every ticket of that batch.
        """
        srv = self._server
        t_end = None if timeout is None else time.perf_counter() + timeout
        while True:
            if not self._done:
                srv._drive()
            if self._done:
                if self._error is not None:
                    raise self._error
                batch = self._batch
                if batch is not None and not batch.finalized:
                    limit = t_end
                    if self.deadline_at is not None:
                        limit = self.deadline_at if limit is None \
                            else min(limit, self.deadline_at)
                    if limit is None:
                        srv._finalize(batch)
                    else:
                        try:
                            srv._wait_batch(
                                batch,
                                max(0.0, limit - time.perf_counter()))
                        except TimeoutError:
                            if self.deadline_at is not None and \
                                    time.perf_counter() >= self.deadline_at:
                                srv._stats.deadline_exceeded += 1
                                self._fail(DeadlineExceeded(
                                    "deadline passed while the batch was "
                                    "still in flight"))
                                raise self._error from None
                            raise
                    # Finalize may have failed or re-queued (retry) this
                    # very ticket — re-examine from the top.
                    continue
                if self._error is not None:
                    raise self._error
                return self._result
            # Not done: queued (possibly backing off for retry) or staged.
            now = time.perf_counter()
            if self.deadline_at is not None and now >= self.deadline_at:
                srv._expire_deadlines()
                if not self._done:
                    srv._stats.deadline_exceeded += 1
                    self._fail(DeadlineExceeded(
                        "deadline passed before dispatch"))
                continue
            if t_end is not None and now >= t_end:
                raise TimeoutError(
                    f"Ticket.result timed out after {timeout:g}s; request "
                    f"still queued for dispatch")
            time.sleep(2.5e-4)

    def _fulfil(self, result, batch: "_Batch") -> None:
        self._result = result
        self._batch = batch
        self._done = True

    def _fail(self, exc: BaseException) -> None:
        self._error = exc
        self._done = True

    def _reset(self) -> None:
        """Return to not-done for a retry / re-dispatch (keeps
        ``submitted_at`` — latency and deadline measure from admission)."""
        self._result = None
        self._error = None
        self._batch = None
        self._done = False


@dataclasses.dataclass
class _Pending:
    req: SCRequest
    ticket: Ticket
    sig: tuple = ()     # shape signature, computed once at admission
    retries: int = 0    # failed-dispatch retries consumed
    not_before: float = 0.0   # earliest re-dispatch time (retry backoff)
    seq: int = -1             # admission serial (trace track identity)
    staged_at: float = 0.0    # last bind into a batch (perf_counter)
    launched_at: float = 0.0  # last dispatch to a device (perf_counter)


class _Batch:
    """One formed bank batch: a template member layout plus bound requests.

    Lives through three states: *staged* (formed, accepting joins into free
    padding slots), *in flight* (dispatched to a device, results async), and
    *finalized* (results ready or failed, tickets resolved)."""

    __slots__ = ("group", "members", "pendings", "slots", "free",
                 "device", "outs", "dispatched_at", "finalized")

    def __init__(self, group: tuple, members: tuple):
        self.group = group
        self.members = members                  # slot -> member ExecutionPlan
        self.pendings: "list[_Pending]" = []
        self.slots: "list[int]" = []            # parallel to pendings
        self.free: "dict[int, deque]" = defaultdict(deque)
        for s, m in enumerate(members):
            self.free[id(m)].append(s)
        self.device = None
        self.outs: "list | None" = None         # per-pending async out dicts
        self.dispatched_at: "float | None" = None
        self.finalized = False

    def bind(self, pending: _Pending, plan) -> bool:
        """Bind ``pending`` (compiled to ``plan``) to a free compatible slot."""
        dq = self.free.get(id(plan))
        if not dq:
            return False
        self.slots.append(dq.popleft())
        self.pendings.append(pending)
        pending.staged_at = time.perf_counter()
        return True

    def unbind(self, idx: int) -> _Pending:
        """Release bound request ``idx`` (staged batches only): its slot
        returns to the free pool as a padding slot."""
        slot = self.slots.pop(idx)
        self.free[id(self.members[slot])].append(slot)
        return self.pendings.pop(idx)

    def ready(self) -> bool:
        """Non-blocking: have all this batch's device results landed?"""
        return all(a.is_ready() for out in self.outs
                   for a in jax.tree_util.tree_leaves(out))


def _percentile(sorted_xs: "list[float]", q: float) -> float:
    if not sorted_xs:
        return 0.0
    idx = min(len(sorted_xs) - 1, max(0, round(q * (len(sorted_xs) - 1))))
    return sorted_xs[idx]


#: Sliding window for latency percentiles (bounds a long-running server's
#: memory; counters stay exact).
LATENCY_WINDOW = 4096
#: LRU caps on the server's own memo/signature state — like the plan/bank
#: caches, serving many bucket shapes must not grow them without bound.
_TEMPLATE_MEMO_CAP = 256
_SIGNATURE_CAP = 4096


@dataclasses.dataclass
class BankServerStats:
    """Cumulative serving counters (reset with ``BankServer.reset_stats``).

    Latencies are kept in a sliding window of the most recent
    ``LATENCY_WINDOW`` requests — p50/p99/mean describe recent traffic, the
    integer counters the server's whole life.  ``exec_s`` is busy wall time:
    the union of intervals during which at least one batch was in flight.
    """

    n_requests: int = 0
    n_batches: int = 0
    bucket_hits: int = 0          # batches whose full exec signature was warm
    bucket_misses: int = 0
    joined_requests: int = 0      # requests continuous-batched into a staged bank
    slots_total: int = 0          # executed template slots (incl. padding)
    active_slots: int = 0         # slots bound to requests
    identity_slots: int = 0       # no-op identity padding slots
    # Compiler-pipeline provenance, summed over every launched batch's bank
    # (per-pass counters the pipeline stages attach to each ExecutionPlan).
    passes_merged: int = 0        # fused passes actually driven (merged bank)
    passes_looped_equiv: int = 0  # passes a per-member loop would have driven
    schedule_cycles: int = 0      # Algorithm-1 scheduled cycles (merged bank)
    passes_fused_away: int = 0    # MUX/XOR/AND fusions + NOT absorptions
    nodes_elided: int = 0         # BUFF elisions + CSE merges
    max_live_peak: int = 0        # peak liveness (scratch slots) over all
    #                               launched banks' group plans
    naive_live_peak: int = 0      # one-row-per-node peak it replaces
    # Reliability counters.
    shed_requests: int = 0        # rejected/shed by admission backpressure
    retries: int = 0              # failed-batch requests re-queued w/ backoff
    quarantines: int = 0          # device circuit-breaker trips
    redispatched_requests: int = 0  # in-flight requests moved off a
    #                                 quarantined device (no retry budget)
    deadline_exceeded: int = 0    # tickets failed by their deadline_ms
    failed_requests: int = 0      # tickets failed by an execution error
    exec_s: float = 0.0           # busy wall time (>=1 batch in flight)
    latencies_s: "deque[float]" = dataclasses.field(
        default_factory=lambda: deque(maxlen=LATENCY_WINDOW))

    def as_dict(self) -> dict:
        lat = sorted(self.latencies_s)
        total_batches = max(self.n_batches, 1)
        return {
            "n_requests": self.n_requests,
            "n_batches": self.n_batches,
            "bucket_hits": self.bucket_hits,
            "bucket_misses": self.bucket_misses,
            "bucket_hit_rate": self.bucket_hits / total_batches,
            "joined_requests": self.joined_requests,
            "padding_waste": (self.slots_total - self.active_slots)
            / max(self.slots_total, 1),
            "identity_slots": self.identity_slots,
            "passes_merged": self.passes_merged,
            "passes_looped_equiv": self.passes_looped_equiv,
            "pass_savings_rate": (self.passes_looped_equiv
                                  - self.passes_merged)
            / max(self.passes_looped_equiv, 1),
            "schedule_cycles": self.schedule_cycles,
            "passes_fused_away": self.passes_fused_away,
            "nodes_elided": self.nodes_elided,
            "max_live_peak": self.max_live_peak,
            "naive_live_peak": self.naive_live_peak,
            "shed_requests": self.shed_requests,
            "retries": self.retries,
            "quarantines": self.quarantines,
            "redispatched_requests": self.redispatched_requests,
            "deadline_exceeded": self.deadline_exceeded,
            "failed_requests": self.failed_requests,
            "p50_ms": _percentile(lat, 0.50) * 1e3,
            "p99_ms": _percentile(lat, 0.99) * 1e3,
            "mean_ms": (sum(lat) / len(lat) * 1e3) if lat else 0.0,
            "throughput_rps": self.n_requests / max(self.exec_s, 1e-9),
            "exec_s": self.exec_s,
        }


_PLACEMENTS = ("affinity", "round_robin", "least_loaded")


class BankServer:
    """Traffic-driven serving engine over bucketed, padded BankPlans.

    Parameters
    ----------
    max_slots:
        Admission threshold: a batch forms as soon as ``max_slots`` requests
        of one execution group are queued.  Joins may bind further requests
        into the batch's padding slots while it is staged.
    window_s:
        Batching window — on submit, if the oldest queued request has waited
        at least this long, the whole queue forms into batches.  ``None``
        (default) disables the time trigger.  The engine is synchronous, so
        the window is evaluated at submit/result/flush calls, not by a
        background thread (0.0 therefore means "never let a request wait
        behind a second submit").
    pad_counts / pad_total:
        Template padding policy (power-of-two slot counts / total).
    key_mode / backend / decode:
        Threaded to the executor; ``decode=True`` (default) returns decoded
        output values per request, else packed streams.
    devices:
        JAX devices to shard batches across (default: all of
        ``jax.devices()``).  Run CPU tests with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to get
        several host devices.
    max_inflight:
        Per-device cap on concurrently in-flight batches (JAX async
        dispatch).  ``0`` degenerates to the synchronous flush-and-wait
        engine of PR-4: every batch blocks before the next dispatch.
    placement:
        ``"affinity"`` (default) prefers devices already warm for the
        batch's member layout, spilling to the least-loaded cold device when
        the warm ones are busy; ``"round_robin"`` cycles; ``"least_loaded"``
        picks the smallest in-flight queue.
    donate:
        Donate the per-batch key buffers to XLA (best-effort; see
        ``executor.execute_bank``).
    max_queue:
        Admission-queue bound (``None`` = unbounded, the historic
        behavior).  At the bound, ``overload`` decides: ``"reject"`` fails
        the *new* request's ticket with :class:`RequestShed` (submit does
        not raise — the shed notice is delivered through the ticket);
        ``"shed_oldest"`` fails the oldest queued request and admits the
        new one.
    max_retries:
        Failed-batch retry budget per request.  A batch failure re-queues
        its requests with exponential backoff
        (``retry_backoff_s * 2**attempt``); past the budget the *original*
        exception fails the ticket.  Retries re-run the identical request
        (same keys), so a successful retry is bit-identical to a clean
        first-shot run.  Default 0: failures propagate immediately.
    quarantine_after / quarantine_s:
        Per-device circuit breaker: after ``quarantine_after`` consecutive
        batch failures on one device it is quarantined for
        ``quarantine_s`` seconds (doubling while it keeps failing its
        health probe).  Its in-flight batches re-dispatch to healthy
        devices without consuming retry budget.  The last healthy device
        is never quarantined.
    fault_injector:
        Chaos hook ``fn(device, batch_or_None)`` called immediately before
        every batch launch (batch) and during health probes (None);
        raising makes the launch/probe fail.  Used by the chaos harness to
        kill devices mid-run.
    trace:
        Observability switch (default None = off, zero overhead on the hot
        path).  Pass a ``core.obs.Trace`` — or ``True`` to have the server
        create one, reachable as ``server.trace`` — and the engine records
        per-request lifecycle spans (``request`` with nested
        ``request.queued`` / ``request.staged`` / ``request.inflight``,
        partitioning admit → stage → launch → reap exactly), ``serve.launch``
        host spans with the executor/compiler spans nested inside, instant
        events for retry / quarantine / re-dispatch / shed / deadline, and
        mirrors the reliability counters into ``trace.metrics`` (folded
        into :meth:`stats` as ``"metrics"``).  Tracing never perturbs
        results — bit-identity on/off is pinned by tests.

    Results are bit-identical per request to standalone
    ``executor.execute[_value]`` with the same key — see module docstring.
    """

    def __init__(self, *, max_slots: int = 8,
                 window_s: "float | None" = None,
                 pad_counts: bool = True, pad_total: bool = True,
                 key_mode: str | None = None, backend: str | None = None,
                 decode: bool = True,
                 devices: "list | None" = None, max_inflight: int = 2,
                 placement: str = "affinity", donate: bool = False,
                 max_queue: "int | None" = None, overload: str = "reject",
                 max_retries: int = 0, retry_backoff_s: float = 0.02,
                 quarantine_after: int = 3, quarantine_s: float = 0.5,
                 fault_injector=None, trace=None):
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if max_inflight < 0:
            raise ValueError("max_inflight must be >= 0")
        if placement not in _PLACEMENTS:
            raise ValueError(f"placement must be one of {_PLACEMENTS}")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None)")
        if overload not in ("reject", "shed_oldest"):
            raise ValueError("overload must be 'reject' or 'shed_oldest'")
        if max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if quarantine_after < 1:
            raise ValueError("quarantine_after must be >= 1")
        self.max_slots = max_slots
        self.window_s = window_s
        self.pad_counts = pad_counts
        self.pad_total = pad_total
        self.key_mode = key_mode
        self.backend = backend
        self.decode = decode
        self.devices = tuple(devices) if devices is not None \
            else tuple(jax.devices())
        if not self.devices:
            raise ValueError("need at least one device")
        self.max_inflight = max_inflight
        self.placement = placement
        self.donate = donate
        self.max_queue = max_queue
        self.overload = overload
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.quarantine_after = quarantine_after
        self.quarantine_s = quarantine_s
        self.fault_injector = fault_injector
        self.trace = obs.Trace("bank-server") if trace is True else trace
        self._req_seq = 0
        # jax's own default placement: when a batch lands here anyway,
        # skipping the explicit commit avoids the committed-argument
        # bookkeeping jit pays per input leaf (measurably slower than the
        # uncommitted fast path, for an identical outcome).
        self._default_device = jax.devices()[0]
        self._queue: "list[_Pending]" = []
        self._staged: "list[_Batch]" = []
        self._inflight: "dict[Any, deque[_Batch]]" = \
            {d: deque() for d in self.devices}
        self._rr = 0
        self._held = False
        self._busy_since: "float | None" = None
        self._closed = False
        self._accepting = True          # False: close() disabled retries
        self._consec_failures: "dict[Any, int]" = {}
        self._quarantined: "dict[Any, float]" = {}   # device -> retest time
        self._quarantine_backoff: "dict[Any, float]" = {}
        # All three maps are LRU-bounded: heterogeneous traffic mints new
        # plan tuples / exec signatures indefinitely, and strong references
        # here must not defeat plan.py's bank-cache cap.
        self._seen_signatures: OrderedDict = OrderedDict()
        # Canonical plan tuple -> padded member layout (plain tuple, cheap):
        # the compiled per-device bank comes from plan.compile_bank_members'
        # own cache at dispatch time.
        self._layout_memo: OrderedDict = OrderedDict()
        # Member layout -> set of devices that have executed it (jit warm).
        self._warm: OrderedDict = OrderedDict()
        self._stats = BankServerStats()
        self._dev_stats = {d: {"n_batches": 0, "n_requests": 0,
                               "quarantines": 0}
                           for d in self.devices}

    # ------------------------------ admission ------------------------------------

    def submit(self, req: SCRequest) -> Ticket:
        """Admit one request; returns immediately with a :class:`Ticket`.

        Batch formation/dispatch runs opportunistically inside the call
        (there is no background thread), but dispatched work proceeds
        asynchronously on its device.  Raises :class:`ServerClosed` after
        ``close()``; under ``max_queue`` backpressure a shed request's
        ticket is returned already failed with :class:`RequestShed`.

        Example::

            import jax
            from repro.core import circuits
            from repro.serve import BankServer, circuit_request
            net = circuits.sc_multiply()
            with BankServer(max_slots=4) as server:
                t = server.submit(circuit_request(
                    net, {"a": 0.5, "b": 0.5}, jax.random.key(0), bl=256))
                out = t.result()           # {"out": ~0.25}
        """
        if self._closed:
            raise ServerClosed("submit() on a closed BankServer")
        _check_fault_args(req.bitflip_rate, req.fault_model, req.flip_key)
        tr = self.trace
        ticket = Ticket(self)
        if req.deadline_ms is not None:
            ticket.deadline_at = \
                ticket.submitted_at + float(req.deadline_ms) / 1e3
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self._pump()        # formation may drain the queue into batches
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self._stats.shed_requests += 1
            if tr is not None:
                tr.metrics.inc("serve.shed_requests")
            if self.overload == "reject":
                if tr is not None:
                    tr.event("serve.shed", policy="reject")
                ticket._fail(RequestShed(
                    f"admission queue full (max_queue={self.max_queue})"))
                return ticket
            oldest = self._queue.pop(0)
            if tr is not None:
                tr.event("serve.shed", policy="shed_oldest", seq=oldest.seq)
            oldest.ticket._fail(RequestShed(
                f"shed by a newer arrival (max_queue={self.max_queue})"))
        p = _Pending(req, ticket, self._shape_sig(req), seq=self._req_seq)
        self._req_seq += 1
        if tr is not None:
            tr.metrics.inc("serve.requests_admitted")
        self._queue.append(p)
        self._pump()
        return ticket

    def serve(self, requests: "list[SCRequest]") -> list:
        """Submit a burst and return its results in request order."""
        tickets = [self.submit(r) for r in requests]
        self.flush()
        return [t.result() for t in tickets]

    def hold(self) -> None:
        """Pause dispatch: batches still form and stage (and keep accepting
        continuous-batching joins) but do not launch until ``release()`` or
        an explicit ``flush()``."""
        self._held = True

    def release(self) -> None:
        """Resume dispatch after :meth:`hold`."""
        self._held = False
        self._pump()

    def flush(self) -> int:
        """Form and dispatch everything queued; returns batches dispatched.

        Does NOT block on results — tickets resolve to async arrays and
        ``Ticket.result()`` performs the wait.  Dispatches even while
        ``hold()`` is in effect.  Requests backing off for retry are left
        queued until their backoff expires."""
        n0 = self._stats.n_batches
        self._expire_deadlines()
        self._check_quarantine()
        self._reap()
        self._join_staged()
        self._form_all()
        while self._staged:
            batch = self._staged.pop(0)
            device = self._pick_device(batch)
            while device is None:
                # Every device is at max_inflight: retire the oldest
                # in-flight batch to free a slot, then place.
                oldest = min((dq[0] for dq in self._inflight.values() if dq),
                             key=lambda b: b.dispatched_at)
                self._finalize(oldest)
                device = self._pick_device(batch)
            self._launch(batch, device)
        return self._stats.n_batches - n0

    def _drive(self) -> None:
        """One blocking-wait scheduler step (Ticket.result's engine)."""
        self.flush()

    # ------------------------------ scheduling -----------------------------------

    def _pump(self) -> None:
        """One scheduler step: reap ready work, join queued requests into
        staged batches, form newly-triggered batches, dispatch while device
        capacity allows.  Called at submit/release boundaries."""
        self._expire_deadlines()
        self._check_quarantine()
        self._reap()
        self._join_staged()
        if self.window_s is not None and self._queue and \
                time.perf_counter() - self._queue[0].ticket.submitted_at \
                >= self.window_s:
            self._form_all()
        else:
            self._form_triggered()
        if not self._held:
            self._dispatch_staged()

    @staticmethod
    def _group_key(req: SCRequest) -> tuple:
        # Static execution parameters that cannot share one bank dispatch.
        # The fault model is normalized (null -> None) so a no-op model
        # batches with clean traffic on the clean jit program.
        return (req.bitstream_length, float(req.bitflip_rate),
                normalize_fault_model(req.fault_model))

    @staticmethod
    def _shape_sig(req: SCRequest) -> tuple:
        # Computed once per request (eagerly by SCRequest, lazily here for
        # plain ExecRequests) — the per-leaf walk is measurable at high
        # admission rates.
        sig = getattr(req, "_layout_sig", None)
        if sig is None:
            sig = _layout_sig_of(req)
            try:
                req._layout_sig = sig
            except AttributeError:
                pass
        return sig

    def _plan_of(self, req: SCRequest, group: tuple):
        # Gate-level fault injection needs the unfused plan (per-gate fkeys).
        return compile_plan(req.net,
                            fuse_mux=not injecting(group[1], group[2])
                            or req.net.is_sequential)

    def _form_triggered(self) -> None:
        # A group that accumulates max_slots waiting requests launches alone —
        # other groups keep building toward their own triggers.
        now = time.perf_counter()
        while True:
            counts: "dict[tuple, int]" = defaultdict(int)
            trigger = None
            for p in self._queue:
                if p.not_before > now:
                    continue
                g = self._group_key(p.req)
                counts[g] += 1
                if counts[g] >= self.max_slots:
                    trigger = g
                    break
            if trigger is None:
                return
            self._form_group(trigger, now)

    def _form_all(self) -> None:
        now = time.perf_counter()
        while True:
            ready = next((p for p in self._queue if p.not_before <= now),
                         None)
            if ready is None:
                return
            self._form_group(self._group_key(ready.req), now)

    def _form_group(self, group: tuple, now: "float | None" = None) -> None:
        if now is None:
            now = time.perf_counter()
        take = [p for p in self._queue if p.not_before <= now
                and self._group_key(p.req) == group][:self.max_slots]
        taken = set(map(id, take))
        self._queue = [p for p in self._queue if id(p) not in taken]
        self._staged.append(self._make_batch(group, take))

    def _make_batch(self, group: tuple, take: "list[_Pending]") -> _Batch:
        plans = [self._plan_of(p.req, group) for p in take]
        # Canonical request order (plan serial, then value shapes): identical
        # traffic mixes bind identically, so the jit signature repeats even
        # when arrival order shuffles.
        order = sorted(range(len(take)),
                       key=lambda i: (plans[i].serial, take[i].sig))
        ordered_plans = tuple(plans[i] for i in order)
        members = self._layout_memo.get(ordered_plans)
        if members is None:
            members = tuple(template_members(list(ordered_plans),
                                             pad_counts=self.pad_counts,
                                             pad_total=self.pad_total))
            self._layout_memo[ordered_plans] = members
            while len(self._layout_memo) > _TEMPLATE_MEMO_CAP:
                self._layout_memo.popitem(last=False)
        else:
            self._layout_memo.move_to_end(ordered_plans)
        batch = _Batch(group, members)
        for i in order:
            bound = batch.bind(take[i], plans[i])
            assert bound, "canonical member layout must fit its own batch"
        return batch

    def _join_staged(self) -> None:
        """Continuous batching: bind queued requests into free padding slots
        of staged (formed, not yet dispatched) batches of the same group."""
        if not self._queue or not self._staged:
            return
        now = time.perf_counter()
        keep: "list[_Pending]" = []
        for p in self._queue:
            if p.not_before > now:      # still backing off: may not join
                keep.append(p)
                continue
            g = self._group_key(p.req)
            plan = None
            for b in self._staged:
                if b.group != g:
                    continue
                if plan is None:
                    plan = self._plan_of(p.req, g)
                if b.bind(p, plan):
                    self._stats.joined_requests += 1
                    break
            else:
                keep.append(p)
        self._queue = keep

    # ------------------------------ placement ------------------------------------

    def _capacity(self, device) -> bool:
        # max_inflight == 0 is the synchronous mode: each launch blocks, so
        # every device is always free by the time placement runs.
        return self.max_inflight == 0 or \
            len(self._inflight[device]) < self.max_inflight

    def _pick_device(self, batch: _Batch):
        """A healthy device with in-flight capacity for ``batch``, or None."""
        devs = self.devices
        if self._quarantined:
            healthy = tuple(d for d in devs if d not in self._quarantined)
            if healthy:         # safety: never strand traffic entirely
                devs = healthy
        if len(devs) == 1:
            return devs[0] if self._capacity(devs[0]) else None
        if self.placement == "round_robin":
            for k in range(len(devs)):
                d = devs[(self._rr + k) % len(devs)]
                if self._capacity(d):
                    self._rr = (self._rr + k + 1) % len(devs)
                    return d
            return None
        cands = [d for d in devs if self._capacity(d)]
        if not cands:
            return None
        if self.placement == "affinity":
            warm = self._warm.get(batch.members)
            warm_free = [d for d in cands if warm and d in warm]
            if warm_free:
                cands = warm_free
        return min(cands, key=lambda d: (len(self._inflight[d]),
                                         devs.index(d)))

    # ------------------------------ execution ------------------------------------

    def _dispatch_staged(self) -> None:
        while self._staged:
            device = self._pick_device(self._staged[0])
            if device is None:
                return
            self._launch(self._staged.pop(0), device)

    def _launch(self, batch: _Batch, device) -> None:
        """Dispatch one batch asynchronously; resolve its tickets.

        Device failures — the ``fault_injector`` hook, errors raised while
        the batch is placed or enqueued, and errors surfacing at
        finalize/``result()`` — run the retry/circuit-breaker policy via
        ``_on_batch_failure``.  When the dispatch raises, the server compiles
        the same program again without the device (``executor.precompile``); if
        that raises too — bad request values, a trace or lowering error, a
        kernel the chip's compiler refuses — the error is the program's: it
        fails the batch's tickets as that error, with no retry and no count
        against the device (``_on_program_error``)."""
        tr = self.trace
        if tr is None:
            self._launch_impl(batch, device)
            return
        # Making the server's trace current for the launch lets the
        # compiler's per-stage spans and the executor's pack/transfer/
        # dispatch spans nest under this host-side launch span.
        with obs.tracing(tr), tr.span("serve.launch", device=str(device),
                                      n_requests=len(batch.pendings),
                                      slots=len(batch.members)):
            self._launch_impl(batch, device)
        tr.metrics.inc("serve.batches_launched")

    def _launch_impl(self, batch: _Batch, device) -> None:
        bl, rate, model = batch.group
        multi = len(self.devices) > 1
        # Per-device template scope partitions the bank cache so each
        # device's jit executable stays keyed to its own bank identity.
        bank = compile_bank_members(batch.members,
                                    scope=device if multi else None)
        n = bank.n_members
        slot_reqs: "list[SCRequest | None]" = [None] * n
        for p, s in zip(batch.pendings, batch.slots):
            slot_reqs[s] = p.req
        active = [r is not None for r in slot_reqs]
        shared = ExecOptions(backend=self.backend, key_mode=self.key_mode,
                             bitstream_length=bl, bitflip_rate=rate,
                             fault_model=model, decode=self.decode)
        sig_order = sorted(range(len(batch.pendings)),
                           key=lambda i: batch.slots[i])
        signature = (bank.serial, bl, rate, model, tuple(active),
                     tuple(batch.pendings[i].sig for i in sig_order))
        hit = signature in self._seen_signatures
        self._seen_signatures[signature] = None
        self._seen_signatures.move_to_end(signature)
        while len(self._seen_signatures) > _SIGNATURE_CAP:
            self._seen_signatures.popitem(last=False)

        t0 = time.perf_counter()
        for p in batch.pendings:
            p.launched_at = t0
        st = self._stats
        st.n_requests += len(batch.pendings)
        st.n_batches += 1
        st.bucket_hits += int(hit)
        st.bucket_misses += int(not hit)
        st.slots_total += n
        st.active_slots += len(batch.pendings)
        st.identity_slots += bank.n_identity_members
        st.passes_merged += bank.n_passes
        st.passes_looped_equiv += bank.n_passes_looped
        st.schedule_cycles += sum(
            _plan_schedule_cycles(g) for g in (bank.comb, bank.seq)
            if g is not None)
        for g in (bank.comb, bank.seq):
            if g is None:
                continue
            st.passes_fused_away += (g.n_fused_mux + g.n_fused_xor
                                     + g.n_fused_and + g.n_not_absorbed)
            st.nodes_elided += g.n_elided
            st.max_live_peak = max(st.max_live_peak, g.max_live)
            st.naive_live_peak = max(st.naive_live_peak, g.naive_live)
        dev_arg = device if multi and device is not self._default_device \
            else None
        if self.fault_injector is not None:
            try:
                self.fault_injector(device, batch)
            except Exception as exc:
                self._on_batch_failure(batch, exc, device)
                return
        kw = dict(template=bank, active=active, device=dev_arg,
                  donate=self.donate, options=shared)
        try:
            outs = executor.run(slot_reqs, **kw)
        except Exception as exc:
            # Whose error?  Compiling the same program without touching the
            # device tells: if that fails too, the program is at fault.
            try:
                executor.precompile(slot_reqs, **kw)
            except Exception as program_exc:
                self._on_program_error(batch, program_exc, device)
            else:
                self._on_batch_failure(batch, exc, device)
            return
        batch.device = device
        batch.dispatched_at = t0
        batch.outs = [outs[s] for s in batch.slots]
        for p, out in zip(batch.pendings, batch.outs):
            p.ticket._fulfil(out, batch)
        if self._busy_since is None:
            self._busy_since = t0
        self._inflight[device].append(batch)
        warm = self._warm.setdefault(batch.members, set())
        warm.add(device)
        self._warm.move_to_end(batch.members)
        while len(self._warm) > _TEMPLATE_MEMO_CAP:
            self._warm.popitem(last=False)
        ds = self._dev_stats[device]
        ds["n_batches"] += 1
        ds["n_requests"] += len(batch.pendings)
        if self.max_inflight == 0:
            self._finalize(batch)

    def _reap(self) -> None:
        """Retire in-flight batches whose results have landed (non-blocking)."""
        for dq in self._inflight.values():
            while dq and dq[0].ready():
                self._finalize(dq[0])

    def _finalize(self, batch: _Batch) -> None:
        """Wait out one in-flight batch; record latencies, or run the
        failure policy (retry / circuit breaker) on its requests."""
        if batch.finalized:
            return
        batch.finalized = True
        err: "BaseException | None" = None
        try:
            jax.block_until_ready(batch.outs)
        except Exception as exc:
            err = exc
        t_done = time.perf_counter()
        dq = self._inflight[batch.device]
        try:
            dq.remove(batch)
        except ValueError:                      # pragma: no cover - safety
            pass
        tr = self.trace
        if err is not None:
            self._on_batch_failure(batch, err, batch.device)
        else:
            self._consec_failures[batch.device] = 0
            for p in batch.pendings:
                t = p.ticket
                if t._error is not None:
                    continue    # already settled (deadline hit mid-flight)
                if t.deadline_at is not None and t_done >= t.deadline_at:
                    self._stats.deadline_exceeded += 1
                    if tr is not None:
                        tr.metrics.inc("serve.deadline_exceeded")
                        tr.event("serve.deadline_exceeded", seq=p.seq,
                                 where="inflight")
                    t._fail(DeadlineExceeded(
                        f"deadline_ms={p.req.deadline_ms:g} passed before "
                        f"the batch completed"))
                    continue
                t.latency_s = t_done - t.submitted_at
                self._stats.latencies_s.append(t.latency_s)
                if tr is not None:
                    self._emit_request_trace(tr, p, t_done, batch)
        if self._busy_since is not None and \
                not any(self._inflight.values()):
            self._stats.exec_s += t_done - self._busy_since
            self._busy_since = None

    def _emit_request_trace(self, tr, p: _Pending, t_done: float,
                            batch: _Batch) -> None:
        """Retroactive lifecycle spans for one reaped request.

        The child spans partition the root exactly — queued (admit → last
        bind), staged (bind → launch), inflight (launch → reap) — so their
        total always accounts for 100% of the request's wall-clock.  Each
        request renders on its own virtual chrome-trace track."""
        t = p.ticket
        t_sub = t.submitted_at
        t_staged = min(max(p.staged_at, t_sub), t_done)
        t_launch = min(max(p.launched_at, t_staged), t_done)
        tid = tr.virtual_tid(f"request-{p.seq}")
        root = tr.add_span("request", t_sub, t_done, tid=tid, seq=p.seq,
                           retries=p.retries, device=str(batch.device))
        tr.add_span("request.queued", t_sub, t_staged, parent=root, tid=tid)
        tr.add_span("request.staged", t_staged, t_launch, parent=root,
                    tid=tid)
        tr.add_span("request.inflight", t_launch, t_done, parent=root,
                    tid=tid)
        m = tr.metrics
        m.inc("serve.requests_completed")
        m.observe("serve.latency_ms", (t_done - t_sub) * 1e3)
        m.observe("serve.queued_ms", (t_staged - t_sub) * 1e3)
        m.observe("serve.staged_ms", (t_launch - t_staged) * 1e3)
        m.observe("serve.inflight_ms", (t_done - t_launch) * 1e3)

    def _wait_batch(self, batch: _Batch, timeout: "float | None") -> None:
        if batch.finalized:
            return
        if timeout is None:
            self._finalize(batch)
            return
        deadline = time.perf_counter() + timeout
        while not batch.ready():
            now = time.perf_counter()
            if now >= deadline:
                raise TimeoutError(
                    f"Ticket.result timed out after {timeout:g}s; batch of "
                    f"{len(batch.pendings)} request(s) still in flight on "
                    f"{batch.device}")
            time.sleep(min(5e-4, deadline - now))
        self._finalize(batch)

    # ------------------------------ reliability ----------------------------------

    @staticmethod
    def _note_exception(exc: BaseException, batch: _Batch, device) -> None:
        # Attach serving context to the ORIGINAL exception (PEP 678) so the
        # user sees both where it failed and what it was doing — without
        # wrapping (isinstance checks and tracebacks stay intact).
        if getattr(exc, "_bankserver_noted", False):
            return
        exc.add_note(f"[BankServer] raised while executing a bank batch of "
                     f"{len(batch.pendings)} request(s) on {device}")
        exc._bankserver_noted = True

    def _on_batch_failure(self, batch: _Batch, exc: BaseException,
                          device) -> None:
        """Failure policy for one failed batch: note the device failure
        (circuit breaker input) and retry or fail each request."""
        batch.finalized = True
        self._note_exception(exc, batch, device)
        self._note_device_failure(device)
        now = time.perf_counter()
        for p in batch.pendings:
            self._retry_or_fail(p, exc, now)

    def _on_program_error(self, batch: _Batch, exc: BaseException,
                          device) -> None:
        """Fail every ticket of a batch whose program could not be built or
        enqueued — no retry (the same program would fail again) and no
        circuit-breaker count (the device did nothing wrong)."""
        batch.finalized = True
        self._note_exception(exc, batch, device)
        for p in batch.pendings:
            self._fail_request(p, exc)

    def _fail_request(self, p: _Pending, exc: BaseException) -> None:
        t = p.ticket
        if t._error is not None:
            return              # already settled (deadline hit mid-flight)
        self._stats.failed_requests += 1
        if self.trace is not None:
            self.trace.metrics.inc("serve.failed_requests")
            self.trace.event("serve.request_failed", seq=p.seq,
                             error=type(exc).__name__)
        t._fail(exc)

    def _retry_or_fail(self, p: _Pending, exc: BaseException,
                       now: float) -> None:
        t = p.ticket
        if t._error is not None:
            return              # already settled (deadline hit mid-flight)
        if self._accepting and p.retries < self.max_retries:
            backoff = self.retry_backoff_s * (2.0 ** p.retries)
            if t.deadline_at is None or now + backoff < t.deadline_at:
                p.retries += 1
                p.not_before = now + backoff
                t._reset()
                self._queue.append(p)
                self._stats.retries += 1
                if self.trace is not None:
                    self.trace.metrics.inc("serve.retries")
                    self.trace.event("serve.retry", seq=p.seq,
                                     attempt=p.retries)
                return
        self._fail_request(p, exc)

    def _note_device_failure(self, device) -> None:
        n = self._consec_failures.get(device, 0) + 1
        self._consec_failures[device] = n
        if n >= self.quarantine_after and device not in self._quarantined:
            healthy = [d for d in self.devices
                       if d not in self._quarantined]
            if len(healthy) > 1:    # never quarantine the last device
                self._quarantine(device)

    def _quarantine(self, device) -> None:
        """Trip the circuit breaker: stop placing batches on ``device`` and
        re-dispatch its in-flight work to healthy devices (no retry budget
        consumed — the requests did nothing wrong)."""
        backoff = self._quarantine_backoff.get(device, self.quarantine_s)
        self._quarantined[device] = time.perf_counter() + backoff
        self._quarantine_backoff[device] = backoff * 2.0
        self._stats.quarantines += 1
        self._dev_stats[device]["quarantines"] += 1
        tr = self.trace
        if tr is not None:
            tr.metrics.inc("serve.quarantines")
            tr.event("serve.quarantine", device=str(device),
                     backoff_s=backoff)
        dq = self._inflight[device]
        while dq:
            b = dq.popleft()
            if b.finalized:                     # pragma: no cover - safety
                continue
            b.finalized = True
            for p in b.pendings:
                if p.ticket._error is not None:
                    continue    # already settled (deadline hit mid-flight)
                p.ticket._reset()
                p.not_before = 0.0
                self._queue.append(p)
                self._stats.redispatched_requests += 1
                if tr is not None:
                    tr.metrics.inc("serve.redispatched_requests")
                    tr.event("serve.redispatch", seq=p.seq,
                             device=str(device))
        if self._busy_since is not None and \
                not any(self._inflight.values()):
            self._stats.exec_s += time.perf_counter() - self._busy_since
            self._busy_since = None

    def _check_quarantine(self) -> None:
        """Health-check quarantined devices whose retest time has come:
        re-admit on a passing probe, else double the quarantine."""
        if not self._quarantined:
            return
        now = time.perf_counter()
        for device, until in list(self._quarantined.items()):
            if now < until:
                continue
            if self._probe(device):
                del self._quarantined[device]
                self._consec_failures[device] = 0
                self._quarantine_backoff.pop(device, None)
            else:
                backoff = self._quarantine_backoff.get(
                    device, self.quarantine_s)
                self._quarantined[device] = now + backoff
                self._quarantine_backoff[device] = backoff * 2.0

    def _probe(self, device) -> bool:
        """One round-trip health check (tiny transfer) on ``device``."""
        try:
            if self.fault_injector is not None:
                self.fault_injector(device, None)
            jax.block_until_ready(jax.device_put(np.uint32(0), device))
            return True
        except Exception:
            return False

    def _expire_deadlines(self) -> None:
        """Fail queued/staged requests whose deadline already passed —
        don't waste a device on work nobody can use."""
        now = time.perf_counter()
        if self._queue and any(
                p.ticket.deadline_at is not None
                and now >= p.ticket.deadline_at for p in self._queue):
            keep: "list[_Pending]" = []
            for p in self._queue:
                dl = p.ticket.deadline_at
                if dl is not None and now >= dl:
                    self._stats.deadline_exceeded += 1
                    if self.trace is not None:
                        self.trace.metrics.inc("serve.deadline_exceeded")
                        self.trace.event("serve.deadline_exceeded",
                                         seq=p.seq, where="queued")
                    p.ticket._fail(DeadlineExceeded(
                        f"deadline_ms={p.req.deadline_ms:g} passed while "
                        f"queued"))
                else:
                    keep.append(p)
            self._queue = keep
        drop = False
        for b in self._staged:
            for i in range(len(b.pendings) - 1, -1, -1):
                t = b.pendings[i].ticket
                if t.deadline_at is not None and now >= t.deadline_at:
                    p = b.unbind(i)
                    self._stats.deadline_exceeded += 1
                    if self.trace is not None:
                        self.trace.metrics.inc("serve.deadline_exceeded")
                        self.trace.event("serve.deadline_exceeded",
                                         seq=p.seq, where="staged")
                    p.ticket._fail(DeadlineExceeded(
                        f"deadline_ms={p.req.deadline_ms:g} passed while "
                        f"staged"))
                    drop = drop or not b.pendings
        if drop:
            self._staged = [b for b in self._staged if b.pendings]

    # ------------------------------ shutdown -------------------------------------

    def close(self, drain: bool = True,
              timeout: "float | None" = None) -> None:
        """Shut the server down; every outstanding ticket settles.

        ``drain=True`` (default) keeps dispatching until every queued,
        staged and in-flight request has a result or a typed error (retries
        and quarantine recovery included; ``timeout`` bounds the drain,
        after which it degrades to the fast path).  ``drain=False`` fails
        undispatched tickets with :class:`ServerClosed`, disables retries,
        and finalizes in-flight batches.  Idempotent; the engine has no
        threads, so nothing can leak."""
        if self._closed:
            return
        self._closed = True
        t_end = None if timeout is None else time.perf_counter() + timeout
        if drain:
            while self._queue or self._staged or \
                    any(self._inflight.values()):
                if t_end is not None and time.perf_counter() >= t_end:
                    drain = False
                    break
                self._drive()
                for dq in list(self._inflight.values()):
                    while dq:
                        self._finalize(dq[0])
                if self._queue and not self._staged:
                    # Everything left is backing off — wait it out.
                    time.sleep(5e-4)
        if not drain:
            self._accepting = False     # no further retries
            for p in self._queue:
                if not p.ticket._done:
                    p.ticket._fail(ServerClosed(
                        "server closed before dispatch"))
            self._queue.clear()
            for b in self._staged:
                for p in b.pendings:
                    if not p.ticket._done:
                        p.ticket._fail(ServerClosed(
                            "server closed before dispatch"))
            self._staged.clear()
            for dq in list(self._inflight.values()):
                while dq:
                    self._finalize(dq[0])

    def __enter__(self) -> "BankServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # Exiting on an exception still drains: tickets must never dangle.
        self.close(drain=True)

    # -------------------------------- stats --------------------------------------

    def stats(self) -> dict:
        """Serving-health snapshot (plain dict, json-serializable).

        Fields are documented exhaustively in ``docs/OBSERVABILITY.md``:
        provenance counters (``n_requests`` / ``n_batches`` / bucket
        hits / joins / padding waste / pass-merge savings), latency
        aggregates (``p50_ms`` / ``p99_ms`` / ``mean_ms`` /
        ``throughput_rps`` over the most recent window), reliability
        counters (``shed_requests`` / ``retries`` / ``quarantines`` /
        ``redispatched_requests`` / ``deadline_exceeded``) and a
        per-device breakdown.  When the server was built with ``trace=``,
        a ``"metrics"`` key carries ``trace.metrics.snapshot()``.

        Example::

            server = BankServer(max_slots=4, trace=True)
            # ... traffic ...
            s = server.stats()
            s["bucket_hit_rate"], s["p99_ms"], s["metrics"]["counters"]
        """
        d = self._stats.as_dict()
        d["n_devices"] = len(self.devices)
        d["devices"] = [{"device": str(dev), **dict(st),
                         "quarantined": dev in self._quarantined}
                        for dev, st in self._dev_stats.items()]
        if self.trace is not None:
            d["metrics"] = self.trace.metrics.snapshot()
        return d

    def reset_stats(self) -> None:
        """Zero the counters; keeps the bucket/jit caches warm (for
        measuring steady-state serving after a warmup pass)."""
        self._stats = BankServerStats()
        self._dev_stats = {d: {"n_batches": 0, "n_requests": 0,
                               "quarantines": 0}
                           for d in self.devices}
        self._busy_since = None
