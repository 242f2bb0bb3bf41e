"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle share,
top device operations and idle gaps named by what the host was doing.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per device operation (``XLA Modules``, one per program, stands in where
a plane has no ops line).  The harness wraps its steps in
``jax.profiler.TraceAnnotation`` (``bench.window`` around the measured window,
``bench.inputs``, ``bench.run_call`` and ``bench.wait`` inside it); those
land on the host plane on the same clock.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"          # where a device has no ops line
WINDOW = "bench.window"
HOST_PREFIX = "bench."


def op_name(text: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = u32[...]
    fusion(...)`` gives ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> dict:
    """Events of one trace (``.xplane.pb``, or gzipped ``.xplane.pb.gz``):
    ``{"devices": {id: [(name, t0, t1)]}, "host": [(name, t0, t1)]}``, times
    in seconds on the trace's clock."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE] or \
                [ln for ln in plane.lines if ln.name == MODULES_LINE]
            evs = devices.setdefault(int(m.group(1)), [])
            for ln in lines:
                evs.extend((op_name(e.name), e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in ln.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def union(intervals, lo: float, hi: float) -> list:
    """Sorted, merged intervals clipped to ``[lo, hi]``."""
    out: list = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of merged ``busy`` intervals within ``[lo, hi]``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(events: dict, chips: int, top: int = 10) -> "dict | None":
    """Busy and idle time of devices ``0 .. chips-1`` over the
    ``bench.window`` annotation.

    Returns None when the trace has no window or no device operation in it:
    a reader then has nothing to read.  ``busy_s`` is the union of each
    device's operation intervals, averaged over the chips; ``idle_share`` is
    one minus busy over the window.  ``top_ops`` sums each operation's time
    over the chips; ``idle_gaps`` sums every device's idle time by the
    harness annotation that overlaps it most (``other`` where none does).
    """
    win = [(t0, t1) for name, t0, t1 in events["host"] if name == WINDOW]
    if not win:
        return None
    lo, hi = win[0]
    window_s = hi - lo
    steps = sorted((t0, t1, n) for n, t0, t1 in events["host"]
                   if n != WINDOW and t1 > lo and t0 < hi)
    starts = [s[0] for s in steps]
    per_dev, op_time, idle_by = {}, {}, {}
    for d in range(chips):
        evs = [(n, max(t0, lo), min(t1, hi))
               for n, t0, t1 in events["devices"].get(d, ())
               if t1 > lo and t0 < hi]
        busy = union([(a, b) for _, a, b in evs], lo, hi)
        per_dev[d] = sum(b - a for a, b in busy)
        for n, a, b in evs:
            op_time[n] = op_time.get(n, 0.0) + (b - a)
        for g0, g1 in gaps(busy, lo, hi):
            best, best_t = "other", 0.0
            # Harness steps run one after another: look from the last step
            # that began before the gap to the last that began inside it.
            j = max(0, bisect.bisect_right(starts, g0) - 1)
            while j < len(steps) and steps[j][0] < g1:
                t0, t1, n = steps[j]
                ov = _overlap(g0, g1, t0, t1)
                if ov > best_t:
                    best, best_t = n, ov
                j += 1
            idle_by[best] = idle_by.get(best, 0.0) + (g1 - g0)
    if not any(per_dev.values()):
        return None
    busy_s = sum(per_dev.values()) / chips
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": busy_s,
            "busy_s_per_device": per_dev,
            "idle_share": 1.0 - busy_s / window_s,
            "top_ops": [[n, t] for n, t in rank(op_time)],
            "idle_gaps": [[n, t] for n, t in rank(idle_by)]}
