"""The benchmark harness: one cell, one run.

Everything a cell is made of is found by name from ``BENCHMARK.json``:

* the configuration: ``configs/<config>.json`` (the entry's ``file``), naming
  the app whose inputs, plain reference and exact value live in
  ``apps/<app>.py``;
* the traffic mix: ``traffic/<traffic>.json``, whose ``kind`` picks the
  general driver below (``frames``: a closed loop of whole frames through
  ``executor.run``);
* each metric: ``metrics/<name>.py``, a reader ``read(record)`` over the
  run's record that returns a number, or None where it finds nothing.

A run makes its inputs from ``--seed``, warms up every program the cell
uses (set-up), measures for ``--seconds``, then compares what the timed
path produced with the plain reference once the window has closed.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: Pixels per reference call: bounds the reference's device memory (LIT:
#: 406 stream rows x 32,768 pixels x 8 words, about 0.4 GB at BL=256).
REF_BLOCK = 1 << 15


class BenchError(Exception):
    """The run cannot proceed: no chip, unknown cell or device, bad spec."""


# ------------------------------------------------------------------ loading


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(spec: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell entry with its configuration and traffic loaded."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / confs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return {"cell": cell, "config": config, "traffic": traffic}


def _load_file(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def app_module(app: str, root: Path = ROOT):
    return _load_file(root / "bench" / "apps" / f"{app}.py",
                      f"bench_app_{app}")


def cell_metrics(spec: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports: end-to-end ones untraced,
    per-layer ones traced; a ``workloads`` key limits an entry to those
    cells, and a per-layer entry without it goes wherever the end-to-end
    metric it moves is reported."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def read_metrics(entries: list, rec: dict, root: Path = ROOT) -> dict:
    out = {}
    for m in entries:
        reader = _load_file(root / "bench" / "metrics" / f"{m['name']}.py",
                            "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ devices


def check_devices(chips: int, require_tpu: bool, root: Path = ROOT) -> list:
    """The cell's devices; raises where the chip or its peaks are missing."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU found: JAX's first device is a "
                         f"{devs[0].platform!r} device; this benchmark "
                         "measures the chip and does not fall back")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devs)}")
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if require_tpu and devs[0].device_kind not in peaks["devices"]:
        raise BenchError(f"device kind {devs[0].device_kind!r} is not in "
                         "bench/peaks.json")
    return devs[:chips]


def peak_memory(devs: list) -> "int | None":
    peaks = []
    for d in devs:
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts traces, backend compiles and persistent-cache loads while
    armed, from JAX's monitoring events."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_hits": "cache_loads"}

    def __init__(self):
        import jax.monitoring as mon

        self.armed = False
        self.counts = {v: 0 for v in self.EVENTS.values()}
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._on)

    def _on(self, event, *args, **kwargs):
        if self.armed and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def close(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on)
        mon.unregister_event_listener(self._on)


# ------------------------------------------------------------------ helpers


class Spans:
    """Harness spans on the host clock, and profiler annotations of the
    same steps when the run is traced."""

    def __init__(self, traced: bool):
        self.spans: list = []
        if traced:
            import jax.profiler
            self._ann = jax.profiler.TraceAnnotation
        else:
            self._ann = None

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = self._ann(name) if self._ann is not None else \
            contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.append((name, t0, time.perf_counter()))


def key_data(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` raw threefry key pairs drawn from the run's seed."""
    return rng.integers(0, 1 << 32, size=(n, 2), dtype=np.uint32)


_REF_FNS: dict = {}


def reference_values(app, bl: int, kd: np.ndarray, inputs: dict,
                     bf16: bool = False) -> np.ndarray:
    """The plain reference's decoded outputs of one frame's ``inputs``
    (``P`` elements each), in blocks of at most ``REF_BLOCK`` elements."""
    import jax

    sc = importlib.import_module("bench.apps.sc")
    p = len(next(iter(inputs.values())))
    per = min(p, REF_BLOCK)
    key = (app.__name__, bl, bf16)
    if key not in _REF_FNS:
        _REF_FNS[key] = jax.jit(
            lambda s, inp, e0: app.reference(s, inp, bl, e0, bf16))
    fn = _REF_FNS[key]
    seed = sc.seed_of(kd)
    out = np.empty(p, np.float32)
    for e0 in range(0, p, per):
        n = min(per, p - e0)
        blk = {k: np.pad(v[e0:e0 + n],
                         [(0, per - n)] + [(0, 0)] * (v.ndim - 1), mode="edge")
               for k, v in inputs.items()}
        out[e0:e0 + n] = np.asarray(fn(seed, blk, np.uint32(e0)))[:n]
    return out


# ------------------------------------------------------------------ drivers


#: Each frame's row of the host diagnostics: its wall time, split by
#: harness step, and this process's CPU time over it (all threads).
HOST_FIELDS = ("wall_ms", "inputs_ms", "run_call_ms", "wait_ms", "cpu_ms")


def drive_frames(ctx: dict) -> dict:
    """Closed loop of whole frames through ``executor.run``: each frame's
    result is on the host before the next starts.

    Frames come from a pool of ``distinct_frames`` made from the seed.  Each
    frame sent is a pool frame with one pixel's inputs taken from another
    pixel of the next pool frame, under a fresh key, both drawn from the
    seed: no cache of inputs or results, keyed on identity or on content,
    can serve a frame from an earlier one.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import executor
    from repro.core.apps import appnet_inputs
    from repro.serve.apps import app_netlist

    cfg, trf, app, rng = ctx["config"], ctx["traffic"], ctx["app"], ctx["rng"]
    h, w = cfg["frame"]
    px = h * w
    bl = cfg["bitstream_length"]
    pool = [app.frame_inputs(rng, h, w) for _ in range(trf["distinct_frames"])]
    net = app_netlist(cfg["app"])
    opts = executor.ExecOptions(bitstream_length=bl, decode=True)
    spans = ctx["spans"]

    def draw(n):
        """Frame ``n``: its pool frame, changed pixel, source pixel, key."""
        p, q = rng.integers(0, px, size=2)
        return n % len(pool), int(p), int(q), key_data(rng, 1)[0]

    def change(frame, tag):
        """Write frame ``tag``'s one-pixel change into ``frame`` (its pool
        frame or a copy); returns what it overwrote."""
        f, p, q, _ = tag
        src = pool[(f + 1) % len(pool)]
        old = {k: v[p].copy() for k, v in frame.items()}
        for k, v in frame.items():
            v[p] = src[k][q]
        return old

    def one(n):
        tag = draw(n)
        frame = pool[tag[0]]
        with spans("bench.inputs"):
            old = change(frame, tag)
            key = jax.random.wrap_key_data(jnp.asarray(tag[3]))
        with spans("bench.run_call"):
            out = executor.run(executor.ExecRequest(
                net, appnet_inputs(cfg["app"], **frame), key, opts))
        with spans("bench.wait"):
            (vals,) = out.values()
            got = np.asarray(vals)
        for k, v in frame.items():                    # the pool as it was
            v[tag[1]] = old[k]
        return tag, got

    one(-1)                                           # compile or load
    ctx["setup_done"]()
    keep, kept = trf["check_sample"], []              # reservoir, seeded
    host = []
    n, t0 = 0, time.perf_counter()
    t_end = t0 + ctx["seconds"]
    with ctx["window"]():
        while True:
            c0 = time.process_time()
            item = one(n)
            steps = [b - a for _, a, b in spans.spans[-3:]]
            t = spans.spans[-1][2]
            host.append([(t - spans.spans[-3][1]) * 1e3,
                         *(d * 1e3 for d in steps),
                         (time.process_time() - c0) * 1e3])
            n += 1
            if len(kept) < keep:
                kept.append(item)
            else:
                j = int(rng.integers(0, n))
                if j < keep:
                    kept[j] = item
            if t >= t_end:
                break
    ctx["window_closed"]()
    rec = {"kind": "frames", "t0": t0, "window_s": t - t0, "attempted": n,
           "failed": 0,
           "frames": {"completed": n, "evals": n * px, "pixels": px},
           "host_per_frame": np.asarray(host)}

    def planned_bytes():
        # The timed program compiled once more ahead of time, for its plan.
        lowered = jax.jit(lambda v, k: executor.run(executor.ExecRequest(
            net, v, k, opts))).lower(appnet_inputs(cfg["app"], **pool[0]),
                                     jax.random.key(0))
        ma = lowered.compile().memory_analysis()
        return {"temp": ma.temp_size_in_bytes,
                "argument": ma.argument_size_in_bytes,
                "output": ma.output_size_in_bytes}

    def check(bf16: bool):
        ref, exact = {}, {}
        for i, (tag, _) in enumerate(kept):
            frame = {k: v.copy() for k, v in pool[tag[0]].items()}
            change(frame, tag)
            ref[i] = reference_values(app, bl, tag[3], frame, bf16)
            exact[i] = app.exact(frame)
        return _compare([(i, got) for i, (_, got) in enumerate(kept)], ref,
                        exact)

    rec["_planned_bytes"] = planned_bytes
    rec["_check"] = check
    return rec


DRIVERS = {"frames": drive_frames}


def frame_diagnostics(host: np.ndarray) -> list:
    """Lines on the host's part in the slowest frame of the window, against
    the median frame: where a frame stalls, and what the host did then."""
    if not len(host):
        return []
    wall = host[:, 0]
    med = float(np.median(wall))
    slow = wall > 1.5 * med
    fmt = lambda row: " ".join(f"{k}={v:.6g}" for k, v in zip(HOST_FIELDS,
                                                                row))
    return [f"frames: {len(wall)}, median {med:.4f} ms; {int(slow.sum())} "
            f"over 1.5x the median, {float((wall[slow] - med).sum()):.4f} ms "
            "over it in all",
            f"median frame: {fmt(np.median(host, 0))}",
            f"slowest frame (#{int(np.argmax(wall))}): "
            f"{fmt(host[int(np.argmax(wall))])}"]


def _compare(got: list, ref: dict, exact: dict) -> dict:
    """Exact comparison of every compared output with the reference.

    ``got``: ``(id, values)`` pairs, ``ref``/``exact`` by id.  Returns the
    number compared (elements that differ) and, for information, the mean
    gap to the exact app."""
    mismatched = sum(int(np.sum(g != ref[i])) for i, g in got)
    elems = sum(int(np.size(g)) for _, g in got)
    gaps = [np.abs(np.asarray(g, np.float64) - exact[i]) for i, g in got]
    mae = float(np.mean(np.concatenate(gaps))) if gaps else math.nan
    return {"mismatched": mismatched, "compared_answers": len(got), "compared_elements": elems,
            "mae_vs_exact": mae}


# ------------------------------------------------------------------ one run


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: "float | None" = None, require_tpu: bool = True,
             root: Path = ROOT, trace_dir: "str | None" = None,
             log=print) -> dict:
    """Run one cell once; returns the result line's object, with the run's
    record under ``_record`` (its ``_check(True)`` reads the control: the
    reference from bfloat16 inputs in the program's place).
    ``require_tpu=False`` skips the look for a chip (CPU tests only).
    """
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec(root)
    parts = cell_parts(spec, workload, root)
    cell, config, traffic = parts["cell"], parts["config"], parts["traffic"]
    if traffic["kind"] not in DRIVERS:
        raise BenchError(f"unknown traffic kind {traffic['kind']!r}")
    metrics = cell_metrics(spec, workload, trace)
    app = app_module(config["app"], root)

    import jax

    devs = check_devices(cell["chips"], require_tpu, root)
    counter = CompileCounter()
    marks = {}
    prof_dir = None
    gcs: list = []                 # [start, seconds] of each collection

    def on_gc(phase, info):
        if phase == "start":
            gcs.append([time.perf_counter(), 0.0])
        elif gcs:
            gcs[-1][1] = time.perf_counter() - gcs[-1][0]

    def setup_done():
        # What set-up built (inputs, requests) lives to the end of the run:
        # keep the collector from walking it again inside the window.
        gc.collect()
        gc.freeze()
        marks["setup_s"] = time.perf_counter() - t_start
        counter.armed = True

    @contextlib.contextmanager
    def window():
        nonlocal prof_dir
        if trace:
            prof_dir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
            # Device operations and the harness's own annotations only.
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
        gc.callbacks.append(on_gc)
        try:
            with spans("bench.window"):
                yield
        finally:
            gc.callbacks.remove(on_gc)
            if trace:
                jax.profiler.stop_trace()

    def window_closed():
        counter.close()
        gc.unfreeze()

    spans = Spans(trace)
    ctx = {"config": config, "traffic": traffic, "app": app,
           "rng": np.random.default_rng(seed), "seconds": float(seconds),
           "devices": devs, "traced": trace, "spans": spans,
           "setup_done": setup_done, "window": window,
           "window_closed": window_closed}
    rec = DRIVERS[traffic["kind"]](ctx)
    rec["setup_s"] = marks["setup_s"]
    rec["harness_spans"] = spans.spans
    rec["compiles_in_window"] = dict(counter.counts)
    mem = peak_memory(devs)
    log(f"bench: {workload} seed={seed} setup_s={rec['setup_s']:.3f} "
        f"window_s={rec['window_s']:.3f} attempted={rec['attempted']} "
        f"failed={rec['failed']}")
    log(f"bench: in the window: {counter.counts['traces']} traces, "
        f"{counter.counts['compiles']} compiles, "
        f"{counter.counts['cache_loads']} cache loads")
    gc_s = [d for _, d in gcs]
    log(f"bench: in the window: {len(gc_s)} garbage collections, "
        f"{sum(gc_s) * 1e3:.4f} ms in all, longest "
        f"{max(gc_s, default=0.0) * 1e3:.4f} ms")
    for line in frame_diagnostics(rec.get("host_per_frame", [])):
        log(f"bench: {line}")
    if trace:
        from . import trace_reduce
        rec["trace"] = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(prof_dir)),
            len(devs))
        if trace_dir is None:
            import shutil
            shutil.rmtree(prof_dir, ignore_errors=True)
    if "_planned_bytes" in rec:
        log(f"bench: planned HBM bytes of the timed program "
            f"(compile().memory_analysis()): {rec['_planned_bytes']()}")
    result_metrics = read_metrics(metrics, rec, root)
    t_chk = time.perf_counter()
    chk = rec["_check"](False)
    limits = config["limits"]
    log(f"bench: compared {chk['compared_answers']} answers "
        f"({chk['compared_elements']} values) in "
        f"{time.perf_counter() - t_chk:.3f} s; mean |decoded - exact| "
        f"{chk['mae_vs_exact']:.6f} (information, not compared)")
    checks = {k: {"value": chk[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": result_metrics,
           "device": device}
    if trace:
        t = rec.get("trace")
        device["busy_s"] = t["busy_s"] if t else 0.0
        device["window_s"] = t["window_s"] if t else rec["window_s"]
        if t:
            out["breakdown"] = {"device_ops": t["top_ops"],
                                "idle_gaps": t["idle_gaps"]}
    out["checks"] = checks
    out["_record"] = rec
    return out


def main(args, t_start: float) -> int:
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start,
                       trace_dir=args.trace_dir,
                       log=lambda m: print(m, flush=True))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    res.pop("_record")
    for k, c in res["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr,
              flush=True)
    print(json.dumps(res), flush=True)
    return 0
