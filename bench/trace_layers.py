#!/usr/bin/env python3
"""Split a profiler trace of a frames window by the program's own layers.

    python3 bench/trace_layers.py <trace dir or .xplane.pb[.gz]> [--chips N]

``trace_reduce`` gives device busy time, idle share and idle gaps by harness
step.  This reads what it cannot: each device operation's ``tf_op`` stat
(the name-scope path of the op, e.g. ``jit(_execute_compiled)/sc.sng/
jit(sng_words)/or:``), which lives in the device plane's event metadata and
which ``jax.profiler.ProfileData`` does not expose, and the program's host
spans (``exec.*``, annotations of ``repro.core.obs`` spans made while an
``obs.Trace`` is current).  The XSpace is parsed with the installed
``protobuf`` from a schema of the few fields read here.

``reduce`` returns, over the ``bench.window`` annotation:

* ``scopes``: device seconds by the first ``sc.*`` component of each op's
  ``tf_op``, and ``unscoped``.  XLA makes some ops without metadata (on a
  TPU, the slice and dynamic-update-slice fusions into which it splits the
  concatenate that stacks streams for a batched pass); each such op is
  counted with the next op of its program execution that has a ``tf_op``
  and the same name stem, else with the next that has one, and its time is
  also summed in ``inferred_s``;
* ``program_spans``: count, total and self seconds of each ``exec.*`` span
  (self time leaves out nested ``exec.*`` spans);
* ``frames_runtime``: per ``run`` id, ``launch_lag`` (the end of its
  ``exec.dispatch`` to the start of the first device program to end after
  the dispatch began) and ``readback`` (that program's end to the end of
  the ``bench.wait`` after the dispatch);
* ``idle_by_span``: each device idle gap put down to the innermost host
  span, program or harness, that overlaps it most (``other`` where none).
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
import statistics
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace_reduce  # noqa: E402

PROGRAM_PREFIX = "exec."
SCOPE_PREFIX = "sc."
UNSCOPED = "unscoped"

# XSpace fields read here (tsl/profiler/protobuf/xplane.proto); a map<K, V>
# field is on the wire a repeated message of key = 1, value = 2.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane")],
    "XPlane": [("name", 2, "string"), ("lines", 3, "XLine"),
               ("event_metadata", 4, "EventMetadataEntry"),
               ("stat_metadata", 5, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64"), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "XEvent")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64"), ("stats", 4, "XStat")],
    "XStat": [("metadata_id", 1, "int64"), ("int64_value", 4, "int64"),
              ("str_value", 5, "string")],
    "XEventMetadata": [("name", 2, "string"), ("stats", 5, "XStat")],
    "XStatMetadata": [("name", 2, "string")],
}
_XSPACE = None


def _xspace_class():
    global _XSPACE
    if _XSPACE is None:
        from google.protobuf import (descriptor_pb2, descriptor_pool,
                                     message_factory)

        fdp = descriptor_pb2.FieldDescriptorProto
        fd = descriptor_pb2.FileDescriptorProto(
            name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
        for msg, fields in _SCHEMA.items():
            d = fd.message_type.add(name=msg)
            for name, number, kind in fields:
                f = d.field.add(name=name, number=number)
                if kind in _SCHEMA:
                    f.type = fdp.TYPE_MESSAGE
                    f.type_name = f".bench_xplane.{kind}"
                    f.label = fdp.LABEL_REPEATED
                else:
                    f.type = getattr(fdp, "TYPE_" + kind.upper())
                    f.label = fdp.LABEL_OPTIONAL
            if msg.endswith("Entry"):
                d.field[1].label = fdp.LABEL_OPTIONAL
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fd)
        _XSPACE = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("bench_xplane.XSpace"))
    return _XSPACE


def _stats(stats, stat_names: dict) -> dict:
    """An event's or its metadata's stats by name: the string or integer
    value (``tf_op`` is a string; the ``run`` argument of a program span,
    which the profiler parses out of ``exec.dispatch#run=3#``, an
    integer)."""
    return {stat_names.get(st.metadata_id): st.str_value or st.int64_value
            for st in stats}


def load(path: str) -> dict:
    """Events of one trace: ``{"ops": {device: [(tf_op, t0, t1, name)]},
    "modules": {device: [(name, t0, t1)]}, "host": [(name, t0, t1, run)]}``,
    times in seconds on the trace's clock, ``tf_op`` None where the op has
    none; host events are the ``bench.*`` and ``exec.*`` ones."""
    p = Path(path)
    if p.is_dir():                      # the newest trace under it
        p = max(list(p.rglob("*.xplane.pb")) + list(p.rglob("*.xplane.pb.gz")),
                key=lambda f: f.stat().st_mtime)
    opener = gzip.open if p.name.endswith(".gz") else open
    with opener(p, "rb") as f:
        xs = _xspace_class()()
        xs.ParseFromString(f.read())
    ops: dict = {}
    modules: dict = {}
    host: list = []
    for plane in xs.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            tf_op = {k: _stats(md.stats, stat_names).get("tf_op")
                     for k, md in meta.items()}
            for ln in plane.lines:
                base = ln.timestamp_ns * 1e-9
                times = [(e.metadata_id, base + e.offset_ps * 1e-12,
                          base + (e.offset_ps + e.duration_ps) * 1e-12)
                         for e in ln.events]
                if ln.name == trace_reduce.OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        (tf_op.get(k), t0, t1,
                         trace_reduce.op_name(meta[k].name))
                        for k, t0, t1 in times)
                elif ln.name == trace_reduce.MODULES_LINE:
                    modules.setdefault(dev, []).extend(
                        (meta[k].name, t0, t1) for k, t0, t1 in times)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                base = ln.timestamp_ns * 1e-9
                for e in ln.events:
                    name = meta[e.metadata_id].name
                    if not name.startswith((trace_reduce.HOST_PREFIX,
                                            PROGRAM_PREFIX)):
                        continue
                    host.append((name, base + e.offset_ps * 1e-12,
                                 base + (e.offset_ps + e.duration_ps) * 1e-12,
                                 _stats(e.stats, stat_names).get("run")))
    return {"ops": ops, "modules": modules, "host": host}


def scope_of(tf_op: str) -> str:
    """The first ``sc.*`` component of a ``tf_op`` (``a/b;c/d`` names the
    ops a fusion holds), or ``unscoped``."""
    for path in tf_op.split(";"):
        for part in path.split("/"):
            if part.startswith(SCOPE_PREFIX):
                return part.rstrip(":")
    return UNSCOPED


def _stem(op: str) -> str:
    """An op's name without its number: ``fusion.12`` gives ``fusion``."""
    return re.sub(r"\.\d+$", "", op)


def _scopes(ops: list, modules: list, lo: float, hi: float) -> tuple:
    """Device seconds by scope over ``[lo, hi]``, and the seconds of ops
    without a ``tf_op``.  Such an op takes the scope of the next op of its
    program execution that has a ``tf_op`` and the same name stem (XLA
    splits one op, a concatenate, into many of one kind, and one of them
    keeps the metadata), failing that of the next op that has one."""
    starts = sorted(m[1] for m in modules)
    out: dict = {}
    inferred = 0.0
    mod, next_any, next_by_stem = None, UNSCOPED, {}
    for tf, t0, t1, name in sorted(ops, key=lambda e: -e[1]):
        m = bisect.bisect_right(starts, t0)
        if m != mod:                  # a new program execution
            mod, next_any, next_by_stem = m, UNSCOPED, {}
        if tf is None:
            scope = next_by_stem.get(_stem(name), next_any)
        else:
            scope = next_any = next_by_stem[_stem(name)] = scope_of(tf)
        d = min(t1, hi) - max(t0, lo)
        if d > 0:
            out[scope] = out.get(scope, 0.0) + d
            if tf is None:
                inferred += d
    return out, inferred


def _program_spans(host: list, lo: float, hi: float) -> dict:
    """Count, total and self seconds of each ``exec.*`` span that starts in
    the window; self time leaves out nested ``exec.*`` spans."""
    evs = sorted((t0, -t1, n) for n, t0, t1, _ in host
                 if n.startswith(PROGRAM_PREFIX) and lo <= t0 < hi)
    out: dict = {}
    stack: list = []          # open spans: [t1, name, child time, t0]
    for t0, neg_t1, n in evs:
        t1 = -neg_t1
        while stack and stack[-1][0] <= t0:
            _close(out, stack.pop())
        if stack:
            stack[-1][2] += t1 - t0
        stack.append([t1, n, 0.0, t0])
    while stack:
        _close(out, stack.pop())
    return out


def _close(out: dict, span: list) -> None:
    t1, n, child, t0 = span
    a = out.setdefault(n, {"count": 0, "total_s": 0.0, "self_s": 0.0})
    a["count"] += 1
    a["total_s"] += t1 - t0
    a["self_s"] += t1 - t0 - child


def _frames_runtime(host: list, modules: list, lo: float, hi: float) -> list:
    """Per ``run`` id in the window: the launch lag and the readback of the
    device program that its ``exec.dispatch`` enqueued, the first to end
    after the dispatch began.  The lag is negative where the program
    started before the call returned; the device's clock is aligned to the
    host's to a fraction of a millisecond."""
    mods = sorted(((t0, t1) for _, t0, t1 in modules), key=lambda m: m[1])
    mod_ends = [m[1] for m in mods]
    waits = sorted((t0, t1) for n, t0, t1, _ in host if n == "bench.wait")
    wait_starts = [w[0] for w in waits]
    out = []
    for n, t0, t1, run in sorted(host, key=lambda e: e[1]):
        if n != "exec.dispatch" or not lo <= t0 < hi:
            continue
        i = bisect.bisect_right(mod_ends, t0)
        j = bisect.bisect_left(wait_starts, t1)
        if i == len(mods) or j == len(waits):
            continue
        m0, m1 = mods[i]
        out.append({"run": run, "launch_lag": m0 - t1,
                    "readback": waits[j][1] - m1})
    return out


def _idle_by_span(ops: dict, host: list, chips: int, lo: float,
                  hi: float) -> dict:
    """Each device's idle gaps in ``[lo, hi]``, each put down whole to the
    innermost host span that overlaps it most: the gap is cut where spans
    open and close, each piece goes to the innermost span open over it, and
    the span with the most pieces' time takes the gap."""
    spans = sorted((t0, t1, n) for n, t0, t1, _ in host
                   if n != trace_reduce.WINDOW and t1 > lo and t0 < hi)
    starts = [s[0] for s in spans]
    longest = max((t1 - t0 for t0, t1, _ in spans), default=0.0)
    out: dict = {}
    for d in range(chips):
        busy = trace_reduce.union([(a, b) for _, a, b, _ in ops.get(d, ())],
                                  lo, hi)
        for g0, g1 in trace_reduce.gaps(busy, lo, hi):
            j0 = bisect.bisect_left(starts, g0 - longest)
            j1 = bisect.bisect_left(starts, g1)
            near = [s for s in spans[j0:j1] if s[1] > g0]
            cuts = sorted({g0, g1} | {t for s in near for t in s[:2]
                                      if g0 < t < g1})
            got: dict = {}
            for a, b in zip(cuts, cuts[1:]):
                mid = (a + b) / 2
                inner = max((s for s in near if s[0] <= mid < s[1]),
                            key=lambda s: (s[0], -s[1]), default=None)
                if inner is not None:
                    got[inner[2]] = got.get(inner[2], 0.0) + (b - a)
            best = max(got, key=got.get) if got else "other"
            out[best] = out.get(best, 0.0) + (g1 - g0)
    return out


def reduce(events: dict, chips: int) -> "dict | None":
    """The four fields above over the ``bench.window`` annotation, or None
    where the trace has no window."""
    win = [(t0, t1) for n, t0, t1, _ in events["host"]
           if n == trace_reduce.WINDOW]
    if not win:
        return None
    lo, hi = win[0]
    scopes: dict = {}
    inferred = 0.0
    for d in range(chips):
        s, i = _scopes(events["ops"].get(d, []),
                       events["modules"].get(d, []), lo, hi)
        for k, v in s.items():
            scopes[k] = scopes.get(k, 0.0) + v / chips
        inferred += i / chips
    return {"scopes": scopes, "inferred_s": inferred,
            "program_spans": _program_spans(events["host"], lo, hi),
            "frames_runtime": _frames_runtime(events["host"],
                                              events["modules"].get(0, []),
                                              lo, hi),
            "idle_by_span": _idle_by_span(events["ops"], events["host"],
                                          chips, lo, hi),
            "frames": sum(1 for n, t0, _, _ in events["host"]
                          if n == "bench.run_call" and lo <= t0 < hi)}


# ------------------------------------------------------------ the numbers


def per_layer(layers: dict) -> dict:
    """The per-layer numbers of a reduced window, each None where its
    field is missing: device ms a frame in SNG and in the logic passes, the
    mean host ms of ``exec.put_values`` and of ``exec.dispatch``'s self
    time, and the medians of ``launch_lag`` and ``readback`` in ms."""
    frames = layers.get("frames") or 0
    scopes, spans = layers.get("scopes", {}), layers.get("program_spans", {})
    rt = layers.get("frames_runtime", [])

    def per_frame(scope):
        return scopes[scope] * 1e3 / frames if frames and scope in scopes \
            else None

    def mean(name, key):
        s = spans.get(name)
        return s[key] * 1e3 / s["count"] if s and s["count"] else None

    def median(key):
        return statistics.median(f[key] for f in rt) * 1e3 if rt else None

    return {"sng_ms_per_frame": per_frame("sc.sng"),
            "pass_ms_per_frame": per_frame("sc.passes"),
            "put_values_ms": mean("exec.put_values", "total_s"),
            "dispatch_ms": mean("exec.dispatch", "self_s"),
            "launch_lag_ms": median("launch_lag"),
            "readback_ms": median("readback")}


def log_lines(layers: dict) -> list:
    """Two lines: device ms a frame by scope; p50/p99/max of the launch lag
    and the readback, naming the frame (``run`` id) of each max."""
    frames = layers.get("frames") or 0
    lines = []
    if frames and layers.get("scopes"):
        by = sorted(layers["scopes"].items(), key=lambda kv: -kv[1])
        lines.append(f"device ms a frame by scope ({frames} frames): " + ", ".join(
            f"{k} {v * 1e3 / frames:.4f}" for k, v in by)
            + f"; counted by order {layers['inferred_s'] * 1e3 / frames:.4f}")
    rt = layers.get("frames_runtime", [])
    if rt:
        parts = []
        for key in ("launch_lag", "readback"):
            v = sorted(f[key] * 1e3 for f in rt)
            worst = max(rt, key=lambda f: f[key])
            p99 = v[min(len(v) - 1, int(0.99 * len(v)))]
            parts.append(f"{key} ms p50 {statistics.median(v):.4f} p99 "
                         f"{p99:.4f} max {v[-1]:.4f} (run {worst['run']})")
        lines.append("TPU runtime: " + "; ".join(parts))
    return lines


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="trace directory or .xplane.pb[.gz] file")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    layers = reduce(load(args.trace), args.chips)
    if layers is None:
        print("trace_layers: no bench.window annotation in the trace",
              file=sys.stderr)
        return 1
    for line in log_lines(layers):
        print(f"trace_layers: {line}")
    print(json.dumps({"per_layer": per_layer(layers), **layers}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
