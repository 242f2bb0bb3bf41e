"""CPU tests of ``bench/trace_layers.py``, the split of a profiler trace by
the program's layers.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Two traces recorded on one TPU v5e are read: a 1 s ``lit_vga.frames``
window (7 frames) recorded with the ``sc.*`` name scopes in the program and
an ``obs.Trace`` current, so that the ``exec.*`` spans are on the profiler's
clock; and the 1 s ``kde_vga.frames`` window of ``test_bench.py``, recorded
before either existed, which stands for a program without them.
"""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace_layers, trace_reduce

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
LIT = str(TESTDATA / "lit_vga_frames_1s.xplane.pb.gz")
KDE = str(TESTDATA / "kde_vga_frames_1s.xplane.pb.gz")
NEW_METRICS = ("sng_ms_per_frame", "pass_ms_per_frame", "put_values_ms",
               "dispatch_ms", "launch_lag_ms", "readback_ms")


@pytest.fixture(scope="module")
def lit():
    return trace_layers.reduce(trace_layers.load(LIT), chips=1)


@pytest.fixture(scope="module")
def lit_old():
    return trace_reduce.reduce(trace_reduce.load(LIT), chips=1)


def test_scopes_cover_device_busy_time(lit, lit_old):
    busy = lit_old["busy_s"]
    assert sum(lit["scopes"].values()) == pytest.approx(busy, rel=1e-5)
    scoped = sum(v for k, v in lit["scopes"].items() if k.startswith("sc."))
    assert scoped >= 0.95 * busy
    assert set(lit["scopes"]) <= {"sc.sng", "sc.passes", "sc.decode",
                                  "sc.faults", "sc.scan", "unscoped"}
    # SNG and the passes are nearly all of it; decode is the rest.
    both = lit["scopes"]["sc.sng"] + lit["scopes"]["sc.passes"]
    assert both == pytest.approx(busy, rel=0.1)
    assert lit["scopes"]["sc.sng"] > lit["scopes"]["sc.passes"]
    # Ops XLA made without metadata were placed by order, and are counted.
    assert 0 < lit["inferred_s"] < 0.25 * busy


def test_program_spans_once_per_frame(lit):
    assert lit["frames"] == 7
    spans = lit["program_spans"]
    for name in ("exec.put_values", "exec.dispatch"):
        assert spans[name]["count"] == lit["frames"]
        assert 0 < spans[name]["self_s"] <= spans[name]["total_s"]


def test_frames_runtime_one_entry_per_frame(lit):
    rt = lit["frames_runtime"]
    assert len(rt) == lit["frames"]
    assert len({f["run"] for f in rt}) == len(rt)
    for f in rt:
        assert f["readback"] >= 0
        # The program starts about when the call returns; the device's
        # clock is aligned to the host's to a fraction of a millisecond.
        assert abs(f["launch_lag"]) < 1e-3


def test_idle_time_by_innermost_span(lit, lit_old):
    idle = lit_old["window_s"] - lit_old["busy_s"]
    assert sum(lit["idle_by_span"].values()) == pytest.approx(idle, rel=1e-4)
    # The per-PI transfers hold the device idle, not the rest of run().
    assert lit["idle_by_span"]["exec.put_values"] > 0.9 * idle


def test_every_new_reading_has_its_number(lit):
    got = trace_layers.per_layer(lit)
    assert set(got) == set(NEW_METRICS)
    assert all(isinstance(got[k], float) for k in NEW_METRICS)
    scopes = lit["scopes"]
    assert got["sng_ms_per_frame"] == pytest.approx(
        scopes["sc.sng"] * 1e3 / 7)
    assert got["put_values_ms"] > got["dispatch_ms"] > 0
    lines = trace_layers.log_lines(lit)
    assert lines[0].startswith("device ms a frame by scope (7 frames): sc.sng")
    assert lines[1].startswith("TPU runtime: launch_lag ms p50 ")
    assert "(run " in lines[1]


def test_trace_without_scopes_or_spans():
    """A program without the scopes and the annotations: every reading of
    them is None, and idle time goes to the harness steps as in
    ``trace_reduce``."""
    layers = trace_layers.reduce(trace_layers.load(KDE), chips=1)
    old = trace_reduce.reduce(trace_reduce.load(KDE), chips=1)
    assert set(layers["scopes"]) == {"unscoped"}
    assert layers["program_spans"] == {} and layers["frames_runtime"] == []
    assert all(v is None for v in trace_layers.per_layer(layers).values())
    assert {n: pytest.approx(t, abs=1e-5) for n, t in old["idle_gaps"]} == \
        layers["idle_by_span"]
    assert trace_layers.per_layer({}) == dict.fromkeys(NEW_METRICS)
    assert trace_layers.reduce({"host": [], "ops": {}, "modules": {}},
                               1) is None


def test_ops_without_metadata_follow_their_kind():
    """Two program executions; an op without a ``tf_op`` takes the scope of
    the next op of its execution with its name stem, else of the next op
    with a ``tf_op``; none after it leaves it unscoped."""
    P = "jit(_execute_compiled)/"
    ops = [(P + "sc.sng/jit(sng_words)/or:", 0.0, 4.0, "shift-left_or_fusion"),
           (None, 4.0, 5.0, "slice_dynamic-update-slice_fusion.7"),
           (P + "sc.sng/slice:", 5.0, 5.5, "fusion.3"),
           (None, 5.5, 6.0, "slice_dynamic-update-slice_fusion.8"),
           (P + "sc.passes/concatenate:", 6.0, 6.5,
            "slice_dynamic-update-slice_fusion.2"),
           (None, 6.5, 7.0, "copy-done"),
           (P + "sc.passes/and:", 7.0, 8.0, "and.1"),
           (P + "sc.decode/reduce_sum:", 8.0, 8.5, "fusion.9"),
           (None, 8.5, 9.0, "copy-start"),
           (None, 10.0, 11.0, "concatenate.1"),
           (P + "sc.sng/select_n:", 11.0, 12.0, "fusion.4")]
    modules = [("jit__execute_compiled(1)", 0.0, 9.0),
               ("jit__execute_compiled(1)", 10.0, 12.0)]
    scopes, inferred = trace_layers._scopes(ops, modules, 0.0, 20.0)
    assert scopes == {"sc.sng": 4.0 + 0.5 + 1.0 + 1.0,
                      "sc.passes": 1.0 + 0.5 + 0.5 + 0.5 + 1.0,
                      "sc.decode": 0.5, "unscoped": 0.5}
    assert inferred == 3.5
    # Clipped to the window.
    scopes, _ = trace_layers._scopes(ops, modules, 3.0, 4.5)
    assert scopes == {"sc.sng": 1.0, "sc.passes": 0.5}


def test_scope_of():
    assert trace_layers.scope_of(
        "jit(_execute_compiled)/sc.sng/jit(sng_words)/or:") == "sc.sng"
    assert trace_layers.scope_of("jit(f)/sc.scan/sc.passes/and:") == "sc.scan"
    assert trace_layers.scope_of("jit(f)/and:;jit(f)/sc.decode/div:") == \
        "sc.decode"
    assert trace_layers.scope_of("jit(f)/concatenate:") == "unscoped"


def test_program_spans_give_self_time():
    host = [("bench.window", 0.0, 10.0, None),
            ("exec.outer", 1.0, 5.0, 1), ("exec.inner", 2.0, 3.0, 1),
            ("exec.inner", 3.5, 4.0, 1), ("exec.outer", 6.0, 7.0, 2),
            ("exec.late", 11.0, 12.0, 3)]
    spans = trace_layers._program_spans(host, 0.0, 10.0)
    assert spans["exec.outer"] == {"count": 2, "total_s": 5.0, "self_s": 3.5}
    assert spans["exec.inner"] == {"count": 2, "total_s": 1.5, "self_s": 1.5}
    assert "exec.late" not in spans


def test_cli_prints_the_lines_and_the_numbers(capsys):
    assert trace_layers.main([LIT]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("trace_layers: device ms a frame by scope")
    assert '"per_layer"' in out[-1]
