"""CPU tests of the benchmark harness, at tiny sizes.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover each metric reader, the host diagnostics, the trace reduction
(on a trace recorded on a TPU v5e, in ``bench/testdata``), the last line,
the refusals (no TPU, unknown device kind, no program beside the
benchmark), the control (the reference from bfloat16 inputs in the
program's place must come out not correct), runs with the timed path
broken underneath, and that a configuration, traffic mix and metric are
found from new files alone.  The look for a chip is skipped in-process
(``require_tpu=False``); everything else runs as on the chip.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness, trace_reduce

ROOT = Path(__file__).resolve().parents[2]
TESTDATA = ROOT / "bench" / "testdata"


def _shrink(root: Path) -> None:
    """Tiny sizes: 32x32 frames."""
    for f in (root / "bench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c["frame"] = [32, 32]
        f.write_text(json.dumps(c))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory) -> Path:
    """A copy of the benchmark beside the program, cut to a CPU size."""
    root = tmp_path_factory.mktemp("tiny")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    os.symlink(ROOT / "src", root / "src")
    _shrink(root)
    return root


def _run(root, workload, seed=2**31 + 77, seconds=1.0, trace=False,
         **kw) -> dict:
    return harness.run_cell(workload, seed, seconds, trace,
                            require_tpu=False, root=root,
                            log=lambda m: None, **kw)


@pytest.fixture(scope="module")
def runs(tiny) -> dict:
    """One untraced run of every cell of one chip (four-chip cells need
    four devices)."""
    spec = harness.load_spec(tiny)
    return {w["name"]: _run(tiny, w["name"]) for w in spec["workloads"]
            if w["chips"] == 1}


# --------------------------------------------------------------- metrics


def _metric(name):
    return harness._load_file(ROOT / "bench" / "metrics" / f"{name}.py",
                              "m_" + name.replace(".", "_"))


FRAMES_REC = {
    "kind": "frames", "t0": 10.0, "window_s": 2.0, "setup_s": 5.5,
    "frames": {"completed": 4, "evals": 400, "pixels": 100},
    "harness_spans": [("bench.run_call", 9.0, 9.5),       # warm-up: left out
                      ("bench.run_call", 10.0, 10.25),
                      ("bench.wait", 10.25, 10.5),
                      ("bench.run_call", 10.5, 10.55)],
    "obs_spans": [],
    "trace": {"busy_s": 1.5, "window_s": 2.0, "idle_share": 0.25},
}
OTHER_REC = {"kind": "other", "t0": 0.0, "window_s": 1.0, "setup_s": 3.0,
             "harness_spans": []}


@pytest.mark.parametrize("name,rec,want", [
    ("setup_s", FRAMES_REC, 5.5),
    ("setup_s", OTHER_REC, 3.0),
    ("evals_per_s", FRAMES_REC, 200.0),
    ("evals_per_s", OTHER_REC, None),
    ("run_call_ms", FRAMES_REC, 150.0),
    ("run_call_ms", OTHER_REC, None),
    ("device_ms_per_frame", FRAMES_REC, 375.0),
    ("device_ms_per_frame", dict(FRAMES_REC, trace=None), None),
    ("device_ms_per_frame", OTHER_REC, None),
    ("idle_share.frames", FRAMES_REC, 25.0),
    ("idle_share.frames", dict(FRAMES_REC, trace=None), None),
    ("idle_share.frames", OTHER_REC, None),
])
def test_metric_readers(name, rec, want):
    got = _metric(name).read(rec)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_every_metric_has_a_reader():
    spec = harness.load_spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


# --------------------------------------------------------------- trace


def test_union_and_gaps():
    busy = trace_reduce.union([(1, 2), (1.5, 3), (5, 6), (-1, 0.5)], 0, 5.5)
    assert busy == [[0, 0.5], [1, 3], [5, 5.5]]
    assert trace_reduce.gaps(busy, 0, 5.5) == [(0.5, 1), (3, 5)]


def test_reduce_names_idle_gaps_by_host_step():
    ev = {"host": [("bench.window", 0.0, 10.0),
                   ("bench.run_call", 0.0, 2.0),
                   ("bench.wait", 2.0, 9.0),
                   ("bench.run_call", 9.0, 10.0)],
          "devices": {0: [("fusion.1", 2.0, 8.0), ("fusion.2", 8.0, 9.0)],
                      1: [("fusion.1", 3.0, 4.0)]}}
    r = trace_reduce.reduce(ev, chips=1)
    assert r["window_s"] == 10.0 and r["busy_s"] == 7.0
    assert r["idle_share"] == pytest.approx(0.3)
    assert r["top_ops"] == [["fusion.1", 6.0], ["fusion.2", 1.0]]
    assert r["idle_gaps"] == [["bench.run_call", 3.0]]
    two = trace_reduce.reduce(ev, chips=2)
    assert two["busy_s"] == pytest.approx(4.0)
    assert trace_reduce.reduce({"host": [], "devices": {}}, 1) is None


def test_reduce_recorded_chip_trace():
    """A 1 s ``kde_vga.frames`` window traced on one TPU v5e (10 frames):
    the device plane found, busy time inside the window, idle gaps named by
    harness steps, operation names without their HLO text."""
    ev = trace_reduce.load(str(TESTDATA / "kde_vga_frames_1s.xplane.pb.gz"))
    assert len(ev["devices"][0]) == 4170
    r = trace_reduce.reduce(ev, chips=1)
    assert r["window_s"] == pytest.approx(0.95889, rel=1e-4)
    assert r["busy_s"] == pytest.approx(0.90484, rel=1e-4)
    assert r["idle_share"] == pytest.approx(0.05637, rel=1e-3)
    assert r["top_ops"][0][0] == "shift-left_or_fusion"
    assert {n for n, _ in r["idle_gaps"]} <= {"bench.run_call", "bench.wait",
                                             "other"}


# --------------------------------------------------------------- runs


def test_runs_are_correct_and_print_the_contract(runs):
    for name, res in runs.items():
        assert res["correct"], (name, res["checks"])
        assert list(res)[:5] == ["correct", "attempted", "failed",
                                 "metrics", "device"]
        assert list(res)[-2:] == ["checks", "_record"]
        assert res["checks"] == {"mismatched": {"value": 0, "limit": 0}}
        assert res["attempted"] > 0 and res["failed"] == 0
        assert "setup_s" in res["metrics"]
        assert res["device"]["count"] == 1
        assert res["_record"]["compiles_in_window"]["compiles"] == 0
        unit = {m["name"]: m["unit"] for m in
                harness.load_spec()["end_to_end"]}
        for k, m in res["metrics"].items():
            assert m["unit"] == unit[k] and np.isfinite(m["value"])


@pytest.mark.parametrize("app", ["lit", "kde"])
def test_same_seed_same_inputs(tiny, app):
    mod = harness.app_module(app, tiny)
    a, b, c = (mod.frame_inputs(np.random.default_rng(s), 4, 6)
               for s in (2**31 + 5, 2**31 + 5, 6))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not all(np.array_equal(a[k], c[k]) for k in a)
    assert next(iter(a.values())).shape[0] == 24
    assert np.array_equal(harness.key_data(np.random.default_rng(9), 3),
                          harness.key_data(np.random.default_rng(9), 3))


@pytest.mark.parametrize("cell", ["lit_vga.frames", "kde_vga.frames"])
def test_control_is_not_correct(runs, cell):
    """The reference from bfloat16 inputs, in the program's place, differs
    from the float32 reference: its reading is above the limit of 0."""
    ctl = runs[cell]["_record"]["_check"](True)
    assert ctl["mismatched"] > 0
    assert ctl["compared_elements"] > 0


def _altered(run):
    """An answer altered where it is produced."""
    def wrapper(*a, **kw):
        return {k: v.at[0].add(1 / 256) for k, v in run(*a, **kw).items()}
    return wrapper


def _half_left_out(run):
    """Half of a frame's pixels left out, the other half's answers in their
    place."""
    def wrapper(*a, **kw):
        out = run(*a, **kw)
        return {k: v.at[v.shape[0] // 2:].set(v[:v.shape[0] - v.shape[0] // 2])
                for k, v in out.items()}
    return wrapper


def _buffer(v):
    """The address of a host array's data (a traced value: its identity)."""
    if hasattr(v, "__array_interface__"):
        return v.__array_interface__["data"][0]
    return id(v)


def _results_cached(run):
    """A frame's answer served from an earlier frame on the same buffer."""
    cache = {}

    def wrapper(req, *a, **kw):
        k = tuple(sorted((n, _buffer(v)) for n, v in req.values.items()))
        if k not in cache:
            cache[k] = run(req, *a, **kw)
        return cache[k]
    return wrapper


def _inputs_cached(run):
    """A copy of each input kept by its buffer and used again for every
    later frame on that buffer."""
    cache = {}

    def wrapper(req, *a, **kw):
        vals = {n: cache.setdefault((n, _buffer(v)), np.array(v))
                if isinstance(v, np.ndarray) else v
                for n, v in req.values.items()}
        return run(dataclasses.replace(req, values=vals), *a, **kw)
    return wrapper


@pytest.mark.parametrize("cell", ["lit_vga.frames", "kde_vga.frames"])
@pytest.mark.parametrize("fault", [_altered, _half_left_out, _results_cached,
                                   _inputs_cached])
def test_broken_timed_path_is_not_correct(tiny, runs, monkeypatch, cell,
                                          fault):
    from repro.core import executor
    monkeypatch.setattr(executor, "run", fault(executor.run))
    res = _run(tiny, cell, seed=2**31 + 901, seconds=0.5)
    assert not res["correct"]
    assert res["checks"]["mismatched"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(tiny, runs):
    res = _run(tiny, "kde_vga.frames", seconds=0.5, trace=True)
    assert res["correct"]
    assert "run_call_ms" in res["metrics"]
    assert "evals_per_s" not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])


def test_host_diagnostics_name_the_slowest_frame(runs):
    host = runs["kde_vga.frames"]["_record"]["host_per_frame"]
    assert host.shape[1] == len(harness.HOST_FIELDS)
    assert np.all(host[:, 0] >= host[:, 1:4].sum(1))  # the steps lie inside
    host = np.ones((5, len(harness.HOST_FIELDS)))
    host[3, 0], host[3, 3] = 40.0, 38.0
    lines = harness.frame_diagnostics(host)
    assert lines[0].startswith("frames: 5, median 1.0000 ms; 1 over")
    assert "39.0000 ms over it" in lines[0]
    assert lines[2].startswith("slowest frame (#3): wall_ms=40 ")
    assert "wait_ms=38" in lines[2]
    assert harness.frame_diagnostics(np.zeros((0, 5))) == []


# --------------------------------------------------------------- refusals


def test_refuses_a_device_that_is_not_a_tpu(tiny, capsys):
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.run_cell("kde_vga.frames", 1, 1.0, False, root=tiny)

    class Args:
        workload, seed, seconds, trace, trace_dir = \
            "kde_vga.frames", 1, 1.0, 0, None
    assert harness.main(Args, 0.0) == 3
    out = capsys.readouterr()
    assert "no TPU" in out.err and "{" not in out.out


def test_refuses_an_unknown_device_kind(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(harness.BenchError, match="peaks"):
        harness.check_devices(1, True)
    with pytest.raises(harness.BenchError, match="asks for 4"):
        harness.check_devices(4, True)


def test_refuses_a_cell_it_does_not_know(tiny):
    with pytest.raises(harness.BenchError, match="unknown workload"):
        _run(tiny, "nope.frames")


def test_benchmark_alone_exits_without_a_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lit_vga.frames",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path,
        capture_output=True, text=True, env=dict(os.environ,
                                                 PYTHONPATH=""))
    assert p.returncode != 0 and p.stdout.strip() == ""


# --------------------------------------------------------------- data


def test_new_config_traffic_and_metric_from_new_files_only(tiny, tmp_path):
    """A later PR adds a cell by adding files and entries: nothing under
    ``bench/`` that is already there is edited."""
    root = tmp_path / "later"
    shutil.copytree(tiny, root, symlinks=True)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    conf = json.loads((root / "bench/configs/kde_vga.json").read_text())
    conf.update(name="kde_tiny", frame=[16, 16], bitstream_length=128)
    (root / "bench/configs/kde_tiny.json").write_text(json.dumps(conf))
    (root / "bench/traffic/frames_two.json").write_text(json.dumps(
        {"kind": "frames", "distinct_frames": 2, "check_sample": 4,
         "why": "two frames"}))
    (root / "bench/metrics/frames_done.py").write_text(
        "def read(rec):\n"
        "    return rec['frames']['completed'] "
        "if rec['kind'] == 'frames' else None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "kde_tiny", "source": "test",
                            "file": "bench/configs/kde_tiny.json",
                            "reduced": ["frame"], "why": "test"})
    spec["workloads"].append({"name": "kde_tiny.frames_two",
                              "config": "kde_tiny", "traffic": "frames_two",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "kde_vga.frames" in m["workloads"]:
            m["workloads"].append("kde_tiny.frames_two")
    spec["per_layer"].append({"name": "frames_done", "unit": "frames",
                              "better": "higher", "source": "host_clock",
                              "layer": "request API",
                              "moves": "evals_per_s",
                              "workloads": ["kde_tiny.frames_two"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    res = _run(root, "kde_tiny.frames_two", seconds=0.3)
    assert res["correct"] and "evals_per_s" in res["metrics"]
    traced = _run(root, "kde_tiny.frames_two", seconds=0.3, trace=True)
    assert traced["metrics"]["frames_done"]["value"] >= 1
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_op_names_drop_the_hlo_text():
    assert trace_reduce.op_name(
        "%shift-left_or_fusion = u32[406,307200,8]{1,2,0} fusion(u32[8])"
    ) == "shift-left_or_fusion"
    assert trace_reduce.op_name("fusion.12") == "fusion.12"
