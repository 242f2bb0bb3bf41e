"""Tracing + metrics layer (core/obs.py) and its hot-path guarantees.

Covers the tentpole contracts of the observability PR:

* span nesting + attrs are correct across worker threads (one shared Trace,
  per-thread open-span stacks, distinct tids);
* ``to_chrome_json`` emits schema-valid chrome://tracing JSON (metadata +
  "X" spans + "i" instants, virtual tracks named);
* the serving engine's counters match a known request trace exactly, and
  its per-request phase spans partition the root request span;
* tracing is observability only: enabling it changes NO bits, under both
  key modes, through the executor and the server;
* each live span is also a profiler annotation carrying its ``run()`` id,
  and each layer of the compiled program carries an ``sc.*`` name scope
  that the compiled HLO keeps.
"""
import json
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apps, circuits, dispatch, executor, obs
from repro.core.appnet import APP_NETLISTS
from repro.core.plan import compile_plan
from repro.serve import BankServer, SCRequest, circuit_request


# ----------------------------- Trace core ----------------------------------

def test_span_nesting_and_attrs():
    tr = obs.Trace("t")
    with tr.span("outer", step=1) as outer:
        with tr.span("inner") as inner:
            inner.set("k", "v")
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    assert spans[0].parent is spans[1]
    assert spans[1].parent is None
    assert spans[1].attrs == {"step": 1}
    assert spans[0].attrs == {"k": "v"}
    assert spans[0].duration_ms <= spans[1].duration_ms


def test_span_nesting_across_threads():
    """Each thread gets its own open-span stack on a shared Trace: a span
    opened on a worker never parents under (or corrupts) the main thread's
    open span, and records the worker's tid."""
    tr = obs.Trace("t")
    done = threading.Event()

    def worker():
        with tr.span("worker-span"):
            pass
        done.set()

    with tr.span("main-span"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert done.wait(1.0)
    by_name = {s.name: s for s in tr.spans()}
    assert by_name["worker-span"].parent is None
    assert by_name["main-span"].parent is None
    assert by_name["worker-span"].tid != by_name["main-span"].tid


def test_module_level_span_noop_when_disabled():
    assert obs.current_trace() is None
    sp = obs.span("anything", x=1)
    assert sp is obs.NULL_SPAN
    with sp:
        sp.set("k", 2)          # inert
    obs.event("nothing")        # no raise, nowhere to go


def test_tracing_context_and_install():
    tr = obs.Trace("ctx")
    with obs.tracing(tr):
        with obs.span("in-ctx"):
            pass
    assert obs.current_trace() is None
    try:
        obs.install(tr)
        with obs.span("installed"):
            pass
    finally:
        obs.install(None)
    assert {s.name for s in tr.spans()} == {"in-ctx", "installed"}


def test_chrome_json_schema():
    tr = obs.Trace("export")
    vt = tr.virtual_tid("track-a")
    with tr.span("live", n=3):
        pass
    tr.add_span("retro", tr.t_origin, tr.t_origin + 0.001, tid=vt, who="me")
    tr.event("ping", code=7)
    doc = json.loads(tr.to_chrome_json(indent=1))
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
        assert {"name", "ph", "pid", "tid"} <= set(e)
    # process_name + one thread_name per virtual track
    meta = {e["name"]: e for e in by_ph["M"]}
    assert meta["process_name"]["args"]["name"] == "export"
    assert meta["thread_name"]["args"]["name"] == "track-a"
    assert meta["thread_name"]["tid"] == vt
    xs = {e["name"]: e for e in by_ph["X"]}
    assert xs["live"]["args"] == {"n": 3}
    assert xs["live"]["dur"] >= 0
    assert xs["retro"]["tid"] == vt
    assert abs(xs["retro"]["dur"] - 1000.0) < 1.0     # 1 ms in us
    (instant,) = by_ph["i"]
    assert instant["name"] == "ping" and instant["s"] == "t"


def test_metrics_registry():
    reg = obs.MetricsRegistry()
    reg.inc("a")
    reg.inc("a", 2)
    reg.gauge("g").set(0.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.observe("h", v)
    snap = reg.snapshot()
    assert snap["counters"] == {"a": 3}
    assert snap["gauges"] == {"g": 0.5}
    h = snap["histograms"]["h"]
    assert h["count"] == 4 and h["sum"] == 10.0 and h["min"] == 1.0
    assert h["max"] == 4.0 and h["p50"] == 3.0
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


# ------------------------- engine counter accuracy --------------------------

def test_server_counters_match_known_trace():
    """6 requests in 2 bursts of 3 (max_slots=4 pads each to one batch):
    the trace's counters, span counts and phase partition must match."""
    net = circuits.sc_multiply()
    keys = jax.random.split(jax.random.key(0), 6)
    with BankServer(max_slots=4, window_s=None, trace=True) as server:
        for burst in (keys[:3], keys[3:]):
            server.serve([circuit_request(net, {"a": 0.3, "b": 0.7}, k, 64)
                          for k in burst])
        tr = server.trace
        counters = server.stats()["metrics"]["counters"]
    assert counters["serve.requests_admitted"] == 6
    assert counters["serve.batches_launched"] == 2
    assert counters["serve.requests_completed"] == 6

    spans = tr.spans()
    roots = [s for s in spans if s.name == "request"]
    assert len(roots) == 6
    assert len([s for s in spans if s.name == "serve.launch"]) == 2
    for root in roots:
        kids = [s for s in spans if s.parent is root]
        assert sorted(k.name for k in kids) == [
            "request.inflight", "request.queued", "request.staged"]
        # exact partition: the three phases cover the root span
        covered = sum(k.duration_ms for k in kids)
        assert covered == pytest.approx(root.duration_ms, rel=1e-6)
        for k in kids:
            assert root.t0 <= k.t0 and k.t1 <= root.t1 + 1e-9
    hist = tr.metrics.snapshot()["histograms"]
    assert hist["serve.latency_ms"]["count"] == 6
    assert hist["serve.queued_ms"]["count"] == 6


def test_compiler_and_exec_spans_via_options_trace():
    tr = obs.Trace("exec")
    opts = executor.ExecOptions(bitstream_length=64, decode=True, trace=tr)
    executor.run(executor.ExecRequest(
        circuits.sc_scaled_add(), {"a": 0.2, "b": 0.8},
        jax.random.key(3), opts))
    names = {s.name for s in tr.spans()}
    assert "exec.dispatch" in names
    # Fresh-compile spans appear only on a cache miss; assert only on the
    # always-present dispatch span plus json validity.
    json.loads(tr.to_chrome_json())


# ------------------------------ bit identity -------------------------------

@pytest.mark.parametrize("key_mode", ["batched", "legacy"])
def test_tracing_changes_no_bits_executor(key_mode):
    net = circuits.sc_sqrt()
    key = jax.random.key(11)
    base = executor.run(executor.ExecRequest(
        net, {"a": 0.4}, key,
        executor.ExecOptions(bitstream_length=128, key_mode=key_mode)))
    tr = obs.Trace("pin")
    traced = executor.run(executor.ExecRequest(
        net, {"a": 0.4}, key,
        executor.ExecOptions(bitstream_length=128, key_mode=key_mode,
                             trace=tr)))
    assert base.keys() == traced.keys()
    for k in base:
        assert bool(jnp.array_equal(base[k], traced[k]))
    assert len(tr.spans()) > 0          # tracing actually happened


@pytest.mark.parametrize("key_mode", ["batched", "legacy"])
def test_tracing_changes_no_bits_server(key_mode):
    net = circuits.sc_multiply()
    keys = jax.random.split(jax.random.key(5), 4)
    opts = executor.ExecOptions(bitstream_length=64, key_mode=key_mode,
                                decode=True)

    def serve(trace):
        with BankServer(max_slots=4, window_s=None, trace=trace) as s:
            return s.serve([SCRequest(net, {"a": 0.6, "b": 0.5}, k,
                                      options=opts)
                            for k in keys])
    base = serve(None)
    traced = serve(True)
    for b, t in zip(base, traced):
        assert b.keys() == t.keys()
        for k in b:
            assert bool(jnp.array_equal(b[k], t[k]))


# --------------------------- profiler annotations ---------------------------

@pytest.fixture
def annotations(monkeypatch):
    """A recording stand-in for ``jax.profiler.TraceAnnotation``: the
    ``(name, kwargs)`` of every annotation entered."""
    entered = []

    class Recorder:
        def __init__(self, name, **kwargs):
            self.args = (name, kwargs)

        def __enter__(self):
            entered.append(self.args)
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(obs, "_Annotation", Recorder)
    return entered


def _lit_frame(pixels=4):
    a = np.random.default_rng(0).uniform(0.05, 0.95, (pixels, 81))
    return apps.appnet_inputs("lit", a=a.astype(np.float32))


def test_spans_enter_annotations_with_their_run_id(annotations):
    tr = obs.Trace("ann")
    with obs.tracing(tr, run=7):
        with obs.span("exec.dispatch", plan="p"):
            pass
    with tr.span("outside-a-run"):
        pass
    assert annotations == [("exec.dispatch", {"run": 7}),
                           ("outside-a-run", {})]
    assert tr.spans()[0].attrs == {"plan": "p", "run": 7}

    # Through run(): each call takes its own id, and the spans of one call
    # share it.
    del annotations[:]
    opts = executor.ExecOptions(bitstream_length=64, decode=True)
    req = executor.ExecRequest(circuits.sc_multiply(),
                               {"a": np.full(4, 0.3, np.float32),
                                "b": np.full(4, 0.6, np.float32)},
                               jax.random.key(1), opts)
    with obs.tracing(tr):
        executor.run(req)
        executor.run(req)
    names = [n for n, _ in annotations]
    assert names.count("exec.put_values") == 2
    assert names.count("exec.dispatch") == 2
    runs = [kw["run"] for n, kw in annotations if n.startswith("exec.")]
    assert runs[0] == runs[1] and runs[2] == runs[3] and runs[0] != runs[2]


def test_no_annotation_without_a_trace(annotations):
    assert obs.current_trace() is None
    with obs.span("exec.dispatch"):
        pass
    executor.run(executor.ExecRequest(
        circuits.sc_multiply(), {"a": 0.3, "b": 0.6}, jax.random.key(1),
        executor.ExecOptions(bitstream_length=64, decode=True)))
    assert annotations == []


def _scopes_in(hlo_text: str) -> set:
    return {m for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for m in re.findall(r"\bsc\.[a-z]+", name)}


def test_compiled_hlo_names_each_layer():
    """The compiled program keeps the layers' name scopes in its ops'
    ``op_name`` metadata, which the profiler reports as ``tf_op``."""
    plan = compile_plan(APP_NETLISTS["lit"]())
    values = {k: jnp.asarray(v) for k, v in _lit_frame().items()}
    text = dispatch._execute_compiled.lower(
        plan, values, jax.random.key(0), None, 256, 0.0, False,
        decode=True).compile().as_text()
    assert {"sc.sng", "sc.passes", "sc.decode"} <= _scopes_in(text)

    seq = compile_plan(circuits.sc_scaled_div())
    assert seq.is_sequential
    text = dispatch._execute_compiled.lower(
        seq, {"a": jnp.float32(0.2), "b": jnp.float32(0.6)},
        jax.random.key(0), jax.random.key(1), 64, 0.01, False,
        decode=True).compile().as_text()
    assert {"sc.sng", "sc.scan", "sc.faults", "sc.decode"} <= \
        _scopes_in(text)


def test_put_values_counts_a_lit_frame():
    tr = obs.Trace("lit")
    pixels = 4
    executor.run(executor.ExecRequest(
        APP_NETLISTS["lit"](), _lit_frame(pixels), jax.random.key(2),
        executor.ExecOptions(bitstream_length=64, decode=True, trace=tr)))
    (put,) = [s for s in tr.spans() if s.name == "exec.put_values"]
    assert put.attrs["arrays"] == 81
    assert put.attrs["bytes"] == 81 * pixels * 4
    (disp,) = [s for s in tr.spans() if s.name == "exec.dispatch"]
    assert put.attrs["run"] == disp.attrs["run"]
    assert put.t1 <= disp.t0
