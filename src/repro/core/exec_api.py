"""Request/options API and the ``run()`` entry point + historic shims.

Top layer of the executor stack (``streams`` <- ``dispatch`` <-
``exec_api`` <- the ``executor`` facade).  Defines the canonical request
types (``ExecOptions``, ``ExecRequest``), the ``run()`` entry point over
them, and the historic ``execute*`` functions as thin shims that build
``ExecRequest``s and delegate to ``run()`` — outputs are bit-identical
(pinned by tests).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax

from . import obs
from .dispatch import (_as_f32, _check_fault_args, _check_modes, _dispatch,
                       _dispatch_binary, _dispatch_many, _execute_compiled,
                       _normalize_batch_shapes, _normalize_keys, _stack_keys,
                       execute_bank, precompile_bank)
from .faults import FaultModel
from .gates import Netlist
from .plan import BankPlan, ExecutionPlan


@dataclasses.dataclass(frozen=True)
class ExecOptions:
    """Frozen execution options shared by every entry point.

    ``backend`` / ``key_mode`` default (``None``) to the module defaults at
    run time; ``flip_key`` is required when ``bitflip_rate > 0``;
    ``batch_shape`` declares the stream batch shape when values alone cannot
    (all-const stream PIs).  ``decode`` fuses the StoB decode into the
    program (the ``execute_value`` behavior); ``binary`` runs the netlist on
    packed binary test-vector words instead of stochastic streams (the
    ``execute_binary`` behavior — ``values`` are then the operand bits and
    the stream fields are ignored).

    ``fault_model`` (a ``core.faults.FaultModel``) generalizes
    ``bitflip_rate`` to the STT-MRAM fault taxonomy — transient flips plus
    stuck-at cells, dead rows/columns and endurance wear — keyed by the same
    ``flip_key`` discipline (required whenever the model has random
    components); the two fields are mutually exclusive.  ``deadline_ms`` is
    a *serving* knob: the bank server bounds the request's total wall time
    (queue + retries + device) by it, failing the ticket with
    ``DeadlineExceeded`` when it passes; the execution paths themselves
    ignore it.

    ``word_chunk`` (words, must divide ``bitstream_length / 32``) streams a
    combinational execution chunk-by-chunk via ``lax.scan`` instead of
    materializing full-length node streams — peak live words drop to about
    ``plan.max_live * word_chunk`` (see the compiler's liveness stage).
    Single-request compiled paths only; bit-identical to unchunked runs.
    ``interpret`` forces Pallas interpret mode on (True) or off (False) for
    the pallas/megakernel backends; ``None`` auto-detects (compiled on TPU,
    interpret elsewhere).

    ``trace`` (a ``core.obs.Trace``, default None = tracing off) makes that
    trace current for the duration of the ``run()`` call, so host-side
    executor spans (value packing, key staging, device transfer, dispatch)
    and compiler per-stage spans land in it.  Tracing never perturbs
    outputs — results are bit-identical with it on or off (pinned by
    tests) — and the field is excluded from options equality, so it does
    not affect batch option-agreement.

    Example::

        from repro.core import circuits, executor, obs
        import jax
        tr = obs.Trace()
        net = circuits.sc_multiply()
        out = executor.run(executor.ExecRequest(
            net, {"a": 0.5, "b": 0.5}, jax.random.key(0),
            executor.ExecOptions(bitstream_length=256, decode=True,
                                 trace=tr)))
        assert "exec.dispatch" in tr.summary()["spans"]
    """

    backend: str | None = None
    key_mode: str | None = None
    bitstream_length: int = 256
    bitflip_rate: float = 0.0
    flip_key: Any = None
    batch_shape: "tuple[int, ...] | None" = None
    decode: bool = False
    binary: bool = False
    fault_model: "FaultModel | None" = None
    deadline_ms: "float | None" = None
    word_chunk: "int | None" = None
    interpret: "bool | None" = None
    trace: Any = dataclasses.field(default=None, compare=False)


@dataclasses.dataclass
class ExecRequest:
    """One canonical execution request: circuit + values + key + options.

    ``net`` is a ``Netlist`` or a prebuilt ``ExecutionPlan`` (compiled
    backends only); ``values`` its PI values (operand bit words under
    ``options.binary``); ``key`` the request's PRNG key — the bit-identity
    anchor: a request produces the same output bits whether it runs
    standalone, inside a merged bank, or bound to a padded template slot on
    any device.  ``serve.SCRequest`` subclasses this with the serving
    layer's flat constructor.

    Example::

        import jax
        from repro.core import circuits, executor
        req = executor.ExecRequest(circuits.sc_multiply(),
                                   {"a": 0.5, "b": 0.5}, jax.random.key(0),
                                   executor.ExecOptions(bitstream_length=512,
                                                        decode=True))
        out = executor.run(req)        # {"out": ~0.25}
    """

    net: Any
    values: dict[str, Any]
    key: Any = None
    options: ExecOptions = dataclasses.field(default_factory=ExecOptions)

    # Flat views of the per-request option fields, so request consumers
    # (serving engine, tests) need not reach through ``options`` for the
    # fields every request carries.
    @property
    def bitstream_length(self) -> int:
        return self.options.bitstream_length

    @property
    def batch_shape(self) -> "tuple[int, ...] | None":
        return self.options.batch_shape

    @property
    def bitflip_rate(self) -> float:
        return self.options.bitflip_rate

    @property
    def flip_key(self):
        return self.options.flip_key

    @property
    def fault_model(self) -> "FaultModel | None":
        return self.options.fault_model

    @property
    def deadline_ms(self) -> "float | None":
        return self.options.deadline_ms


# -------------------------------- shim API ----------------------------------------

def execute(net: Netlist, values: dict[str, jax.Array], key: jax.Array,
            bitstream_length: int, bitflip_rate: float = 0.0,
            flip_key: jax.Array | None = None,
            backend: str | None = None, key_mode: str | None = None,
            batch_shape: tuple[int, ...] | None = None,
            fault_model: "FaultModel | None" = None) -> dict[str, jax.Array]:
    """Execute a (possibly sequential) netlist; returns packed output streams.

    ``bitflip_rate`` injects faults on the PI streams and on every gate
    output stream (the paper injects at input/output nodes of the
    arithmetic operations); ``fault_model`` generalizes it to the STT-MRAM
    taxonomy (stuck-at, dead regions, wear — ``core/faults.py``), keyed by
    the same ``flip_key``.  ``backend`` selects the execution engine (see
    ``executor`` module docstring); all backends are bit-identical.
    ``key_mode`` selects the stream-generation key discipline (``"batched"``
    default — one fused SNG pass for all PI streams; ``"legacy"`` — one PRNG
    split per stream, bit-exactly the pre-batching behavior); both backends
    honor it identically.  ``batch_shape`` declares the stream batch shape
    when it is not derivable from ``values`` (e.g. all stream PIs
    const-valued).

    Thin shim over ``run()``: builds one ``ExecRequest`` — bit-identical.
    """
    return run(ExecRequest(net, values, key, ExecOptions(
        backend=backend, key_mode=key_mode,
        bitstream_length=bitstream_length, bitflip_rate=bitflip_rate,
        flip_key=flip_key, batch_shape=batch_shape,
        fault_model=fault_model)))


def execute_value(net: Netlist, values: dict[str, jax.Array], key: jax.Array,
                  bitstream_length: int, bitflip_rate: float = 0.0,
                  flip_key: jax.Array | None = None,
                  backend: str | None = None, key_mode: str | None = None,
                  batch_shape: tuple[int, ...] | None = None,
                  fault_model: "FaultModel | None" = None) -> dict[str, jax.Array]:
    """Execute and decode each output stream to its unipolar value.

    On the compiled backends the decode is fused into the execution program
    (single dispatch per call).  Thin shim over ``run()``."""
    return run(ExecRequest(net, values, key, ExecOptions(
        backend=backend, key_mode=key_mode,
        bitstream_length=bitstream_length, bitflip_rate=bitflip_rate,
        flip_key=flip_key, batch_shape=batch_shape, decode=True,
        fault_model=fault_model)))


def execute_binary(net: Netlist, operand_bits: dict[str, jax.Array],
                   backend: str | None = None) -> dict[str, jax.Array]:
    """Execute a binary netlist on packed test-vector words.

    ``operand_bits`` maps PI names to uint32 words whose lane ``t`` is the
    PI's value in test vector ``t``.  Constant PIs (const_value set) are
    filled automatically.  Inverted-polarity storage (the Fig. 7(a) trick) is
    applied by the *caller* via the netlist's value conventions.

    Thin shim over ``run()`` (``options.binary``) — bit-identical.
    """
    return run(ExecRequest(net, dict(operand_bits), options=ExecOptions(
        backend=backend, binary=True)))


#: Legacy positional tail of execute_many/execute_value_many after
#: (nets, values_seq); the *args/**kwargs shim reassembles it so the
#: deprecated plural-kwarg spellings (keys=/batch_shapes=) can be detected.
_MANY_TAIL = ("keys", "bitstream_length", "bitflip_rate", "flip_keys",
              "backend", "key_mode", "batch_shapes")


def _many_tail(fn_name: str, args: tuple, kwargs: dict) -> tuple:
    for bad in ("keys", "batch_shapes"):
        if bad in kwargs:
            warnings.warn(
                f"{fn_name}({bad}=...) is deprecated: build per-member "
                f"ExecRequests (each carrying its own key / "
                f"options.batch_shape) and call executor.run([...])",
                DeprecationWarning, stacklevel=3)
    if len(args) > len(_MANY_TAIL):
        raise TypeError(f"{fn_name}: too many positional arguments")
    params = dict(zip(_MANY_TAIL, args))
    dup = sorted(set(params) & set(kwargs))
    if dup:
        raise TypeError(f"{fn_name}: got multiple values for {dup}")
    params.update(kwargs)
    unknown = sorted(set(params) - set(_MANY_TAIL))
    if unknown:
        raise TypeError(f"{fn_name}: unexpected keyword arguments {unknown}")
    missing = sorted({"keys", "bitstream_length"} - set(params))
    if missing:
        raise TypeError(f"{fn_name}: missing required arguments {missing}")
    return (params["keys"], params["bitstream_length"],
            params.get("bitflip_rate", 0.0), params.get("flip_keys"),
            params.get("backend"), params.get("key_mode"),
            params.get("batch_shapes"))


def _many_shim(fn_name: str, nets, values_seq, args, kwargs,
               decode: bool) -> list:
    """Shared execute_many/execute_value_many shim: build per-member
    ``ExecRequest``s and delegate to ``run()`` — bit-identical to the legacy
    plural-kwarg path (stacking per-member key rows reproduces the original
    key array exactly)."""
    (keys, bitstream_length, bitflip_rate, flip_keys, backend, key_mode,
     batch_shapes) = _many_tail(fn_name, args, kwargs)
    n = len(nets)
    if n == 0:
        raise ValueError("execute_many: need at least one netlist")
    if len(values_seq) != n:
        raise ValueError(f"values: got {len(values_seq)} for {n} netlists")
    keys = _normalize_keys(keys, n)
    batch_shapes = _normalize_batch_shapes(batch_shapes, n)
    if bitflip_rate > 0.0:
        if flip_keys is None:
            raise ValueError("bitflip_rate > 0 requires flip_keys")
        flip_keys = _normalize_keys(flip_keys, n, "flip_keys")
    reqs = [ExecRequest(net, vals, keys[i], ExecOptions(
                backend=backend, key_mode=key_mode,
                bitstream_length=bitstream_length,
                bitflip_rate=bitflip_rate,
                flip_key=flip_keys[i] if bitflip_rate > 0.0 else None,
                batch_shape=batch_shapes[i] if batch_shapes else None,
                decode=decode))
            for i, (net, vals) in enumerate(zip(nets, values_seq))]
    return run(reqs)


def execute_many(nets, values_seq, /, *args, **kwargs) -> list:
    """Execute N (possibly different) netlists as ONE fused bank-level plan.

    Legacy signature: ``execute_many(nets, values_seq, keys,
    bitstream_length, bitflip_rate=0.0, flip_keys=None, backend=None,
    key_mode=None, batch_shapes=None)``.

    ``nets[i]`` runs with PI values ``values_seq[i]`` and PRNG key ``keys[i]``
    (``keys`` may also be a single key, which is split N ways).  Returns one
    packed-output dict per member, bit-identical to calling ``execute`` per
    netlist with the same per-member keys and ``key_mode`` — the merged plan
    batches same-type gates of each level *across* members (core/plan.py bank
    merging), and in batched key mode all members' PI streams generate in one
    fused SNG pass per distinct batch shape, so the whole bank runs in a
    single jit dispatch instead of N.  Member batch shapes may differ
    (``batch_shapes[i]`` declares member i's shape when its values alone
    cannot, e.g. all-const stream PIs).  ``bitflip_rate`` injects per-member
    faults keyed by ``flip_keys[i]`` (single key allowed, split N ways).

    .. deprecated:: the plural-kwarg spellings ``keys=`` / ``batch_shapes=``
       — build per-member ``ExecRequest``s and call ``run([...])`` instead;
       this shim stays bit-identical but warns.
    """
    return _many_shim("execute_many", nets, values_seq, args, kwargs,
                      decode=False)


def execute_value_many(nets, values_seq, /, *args, **kwargs) -> list:
    """``execute_many`` with the StoB decode fused into the same program.

    Same legacy signature and deprecation notes as ``execute_many``.
    """
    return _many_shim("execute_value_many", nets, values_seq, args, kwargs,
                      decode=True)


# ------------------------------ run() entry point ---------------------------------

_SHARED_OPTION_FIELDS = ("backend", "key_mode", "bitstream_length",
                         "bitflip_rate", "decode", "binary", "fault_model",
                         "word_chunk", "interpret")


def _common_options(reqs: "list[ExecRequest]") -> ExecOptions:
    """The options every request of a merged batch must agree on (per-slot
    fields — key, flip_key, batch_shape, values — stay per request)."""
    o0 = reqs[0].options
    for r in reqs[1:]:
        for f in _SHARED_OPTION_FIELDS:
            if getattr(r.options, f) != getattr(o0, f):
                raise ValueError(
                    f"run: requests disagree on options.{f}: "
                    f"{getattr(o0, f)!r} vs {getattr(r.options, f)!r} "
                    f"(group requests by shared options, or pass options=)")
    return o0


def _run_one(req: ExecRequest, device=None,
             options: ExecOptions | None = None):
    o = options or req.options
    if o.binary:
        return _dispatch_binary(req.net, req.values, o.backend)
    values, key, flip_key = req.values, req.key, o.flip_key
    if device is not None:
        # Commit only the key(s): jit places the program with its committed
        # argument, and uncommitted values follow in one transfer (committing
        # a values pytree leaf-by-leaf costs more than the dispatch).
        with obs.span("exec.device_transfer", device=str(device)):
            key = jax.device_put(key, device)
            if flip_key is not None:
                flip_key = jax.device_put(flip_key, device)
    if isinstance(req.net, ExecutionPlan):
        backend, key_mode = _check_modes(o.backend, o.key_mode)
        if backend == "reference":
            raise ValueError("the reference backend interprets netlists; "
                             "pass the Netlist, not its ExecutionPlan")
        fault_model = _check_fault_args(o.bitflip_rate, o.fault_model,
                                        flip_key)
        batch_shape = (tuple(o.batch_shape)
                       if o.batch_shape is not None else None)
        values = {k: _as_f32(v) for k, v in values.items()}
        with obs.span("exec.dispatch", plan=req.net.name,
                      bitstream_length=o.bitstream_length):
            return _execute_compiled(
                req.net, values, key, flip_key,
                o.bitstream_length, float(o.bitflip_rate),
                backend == "compiled_pallas", decode=o.decode,
                key_mode=key_mode, batch_shape=batch_shape,
                fault_model=fault_model, word_chunk=o.word_chunk,
                megakernel=backend == "compiled_megakernel",
                interpret=o.interpret)
    return _dispatch(req.net, values, key, o.bitstream_length,
                     o.bitflip_rate, flip_key, o.backend, decode=o.decode,
                     key_mode=o.key_mode, batch_shape=o.batch_shape,
                     fault_model=o.fault_model, word_chunk=o.word_chunk,
                     interpret=o.interpret)


def _run_many(reqs: "list[ExecRequest]", device=None,
              options: ExecOptions | None = None) -> list:
    if not reqs:
        raise ValueError("run: need at least one request")
    shared = options or _common_options(reqs)
    if shared.binary:
        raise ValueError("run: binary requests execute one at a time")
    if shared.word_chunk is not None:
        raise ValueError("run: word_chunk streams single-plan executions; "
                         "bank-merged batches run unchunked")
    for r in reqs:
        if not isinstance(r.net, Netlist):
            raise TypeError("run([...]) merges netlists into one bank; pass "
                            "template= to execute a prebuilt BankPlan")
    rate = float(shared.bitflip_rate)
    model = shared.fault_model
    flip_keys = None
    if rate > 0.0 or (model is not None and model.needs_keys):
        flip_keys = [r.options.flip_key for r in reqs]
        if any(fk is None for fk in flip_keys):
            raise ValueError("fault injection requires a flip_key on every "
                             "request")
    batch_shapes = [r.options.batch_shape for r in reqs]
    if all(b is None for b in batch_shapes):
        batch_shapes = None
    values_seq = [r.values for r in reqs]
    keys = [r.key for r in reqs]
    if device is not None:
        # Commit only the keys (see _run_one): the program follows them.
        with obs.span("exec.device_transfer", device=str(device)):
            keys = jax.device_put(keys, device)
            if flip_keys is not None:
                flip_keys = jax.device_put(flip_keys, device)
    return _dispatch_many([r.net for r in reqs], values_seq, keys,
                          shared.bitstream_length, rate, flip_keys,
                          shared.backend, shared.decode,
                          key_mode=shared.key_mode,
                          batch_shapes=batch_shapes, fault_model=model,
                          interpret=shared.interpret)


def _run_template(reqs, bank: BankPlan, active=None, device=None,
                  donate: bool = False,
                  options: ExecOptions | None = None) -> list:
    """Slot-aligned template execution: ``reqs[i]`` feeds template slot ``i``
    (``None`` = unbound slot, masked out)."""
    args, kw = _template_args(reqs, bank, active, options)
    return execute_bank(*args, device=device, donate=donate, **kw)


def precompile(slot_reqs, *, template: BankPlan, active=None, device=None,
               donate: bool = False,
               options: ExecOptions | None = None) -> None:
    """Compile what ``run(slot_reqs, template=...)`` with the same arguments
    would run, without running it (``dispatch.precompile_bank``).

    Raises the program's own errors — bad request values, a trace or
    lowering error, a kernel the compiler refuses — and none of a device's;
    a ``run`` with the same arguments finds the executable in jit's cache.
    """
    args, kw = _template_args(list(slot_reqs), template, active, options)
    precompile_bank(*args, device=device, donate=donate, **kw)


def _template_args(reqs, bank: BankPlan, active,
                   options: ExecOptions | None) -> "tuple[tuple, dict]":
    """``execute_bank``'s arguments for slot-aligned requests, bar
    ``device`` and ``donate``."""
    n = bank.n_members
    if len(reqs) != n:
        raise ValueError(f"run: got {len(reqs)} slot requests for {n} slots")
    bound = [(i, r) for i, r in enumerate(reqs) if r is not None]
    if not bound:
        raise ValueError("run: template batch needs at least one bound slot")
    shared = options or _common_options([r for _, r in bound])
    if shared.binary:
        raise ValueError("run: binary requests execute one at a time")
    if shared.word_chunk is not None:
        raise ValueError("run: word_chunk streams single-plan executions; "
                         "template banks run unchunked")
    rate = float(shared.bitflip_rate)
    model = shared.fault_model
    need_keys = rate > 0.0 or (model is not None and model.needs_keys)
    if active is None:
        active = [r is not None for r in reqs]
    # Placeholder rows for unbound slots: any same-impl key works (masked
    # slots draw no streams); reusing the first bound key row unwraps once.
    key0 = bound[0][1].key
    fk0 = bound[0][1].options.flip_key
    values_seq: list = [{} for _ in range(n)]
    key_rows: list = [key0] * n
    flip_rows: list = [fk0 if fk0 is not None else key0] * n
    batch_shapes: list = [None] * n
    for i, r in bound:
        values_seq[i] = r.values
        key_rows[i] = r.key
        batch_shapes[i] = r.options.batch_shape
        if need_keys:
            if r.options.flip_key is None:
                raise ValueError("fault injection requires a flip_key on "
                                 "every request")
            flip_rows[i] = r.options.flip_key
    return ((bank, values_seq, _stack_keys(key_rows),
             shared.bitstream_length),
            dict(active=active, bitflip_rate=rate,
                 flip_keys=_stack_keys(flip_rows) if need_keys else None,
                 backend=shared.backend, key_mode=shared.key_mode,
                 batch_shapes=batch_shapes, decode=shared.decode,
                 fault_model=model, interpret=shared.interpret))


def run(request_or_requests, *, template: BankPlan | None = None,
        active=None, device=None, donate: bool = False,
        options: ExecOptions | None = None):
    """Canonical execution entry point over ``ExecRequest``s.

    * ``run(req)`` — execute one request (netlist or prebuilt plan);
      returns its output dict (decoded when ``options.decode``).
    * ``run([req, ...])`` — merge the requests' netlists into ONE fused
      bank-level program (the ``execute_many`` path); returns one output
      dict per request, bit-identical to running each alone.
    * ``run(slot_reqs, template=bank)`` — bind slot-aligned requests
      (``None`` = unbound) onto a padded bank template and execute with the
      unbound slots masked; returns one entry per slot (``None`` where
      unbound).  This is the serving engine's path.

    Batch paths require the requests to agree on the shared option fields
    (backend / key_mode / bitstream_length / bitflip_rate / decode); pass
    ``options=`` to supply them explicitly instead (per-slot key, flip_key,
    batch_shape and values always come from each request).  ``device``
    commits the batch inputs to one JAX device before dispatch;
    ``donate`` forwards to ``execute_bank`` (template path only).

    ``key`` semantics are the bit-identity anchor: a request's output bits
    depend only on its own key (and ``key_mode``), never on which batch,
    slot, or device it executed in.

    Example::

        import jax
        from repro.core import circuits, executor
        net = circuits.sc_multiply()
        req = executor.ExecRequest(net, {"a": 0.25, "b": 0.5},
                                   jax.random.key(7),
                                   executor.ExecOptions(decode=True))
        alone = executor.run(req)
        merged = executor.run([req, req])      # one fused bank program
        assert float(alone["out"]) == float(merged[0]["out"])
    """
    if isinstance(request_or_requests, ExecRequest):
        reqs: "list[ExecRequest]" = [request_or_requests]
        single = True
    else:
        reqs = list(request_or_requests)
        single = False
    tr = options.trace if options is not None and options.trace is not None \
        else next((r.options.trace for r in reqs
                   if r is not None and r.options.trace is not None), None)
    if tr is None:
        return _run_any(reqs, single, template, active, device, donate,
                        options)
    with obs.tracing(tr):
        return _run_any(reqs, single, template, active, device, donate,
                        options)


def _run_any(reqs, single, template, active, device, donate, options):
    if single:
        return _run_one(reqs[0], device=device, options=options)
    if template is not None:
        return _run_template(reqs, template, active=active, device=device,
                             donate=donate, options=options)
    return _run_many(reqs, device=device, options=options)
