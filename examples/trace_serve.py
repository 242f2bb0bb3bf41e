"""Traced bursty serving: a chrome://tracing view of where request time goes.

Runnable entry point for the observability layer (docs/OBSERVABILITY.md):

    PYTHONPATH=src python examples/trace_serve.py

Replays the examples/serve_sc.py traffic shape — bursts of local-image-
thresholding windows (LIT) and kernel-density estimates (KDE) with shifting
composition — through a ``BankServer(trace=True)``.  The engine records one
root ``request`` span per served request (on its own virtual track) with
``request.queued`` / ``request.staged`` / ``request.inflight`` children
partitioning the admit -> bucket/stage -> launch -> reap lifecycle, plus the
compiler-stage and executor spans that fire inside each ``serve.launch``.

The script writes ``trace_serve.json`` (load it at chrome://tracing or
https://ui.perfetto.dev) and sanity-checks the trace before declaring
victory: every request's phase spans must nest inside its root span and sum
to >= 90% of the request's measured wall-clock.
"""
import json
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.apps import KDE_N
from repro.serve import BankServer, app_request

BL = 256
# Bursty traffic: (n_lit, n_kde) per burst — composition shifts burst to
# burst but revisits earlier mixes (what the bucketing rewards).
BURSTS = [(3, 1), (1, 3), (2, 2), (3, 1), (1, 3), (2, 2)]
OUT = "trace_serve.json"


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    server = BankServer(max_slots=8, window_s=None, trace=True)
    key = jax.random.key(42)

    t0 = time.perf_counter()
    for n_lit, n_kde in BURSTS:
        reqs = []
        for _ in range(n_lit):
            key, sub = jax.random.split(key)
            reqs.append(app_request("lit", sub, BL,
                                    a=rng.uniform(0.1, 0.9, size=(81,))))
        for _ in range(n_kde):
            key, sub = jax.random.split(key)
            reqs.append(app_request("kde", sub, BL,
                                    x_t=float(rng.uniform(0.2, 0.8)),
                                    hist=rng.uniform(0.2, 0.8, size=(KDE_N,))))
        server.serve(reqs)
    wall_ms = (time.perf_counter() - t0) * 1e3

    tr = server.trace
    chrome = tr.to_chrome_json(indent=1)
    json.loads(chrome)                       # must be loadable JSON
    with open(OUT, "w") as f:
        f.write(chrome)

    # -- sanity-check the per-request lifecycle spans ----------------------
    spans = tr.spans()
    roots = [sp for sp in spans if sp.name == "request"]
    n_requests = sum(a + b for a, b in BURSTS)
    assert len(roots) == n_requests, (len(roots), n_requests)

    phase_names = ("request.queued", "request.staged", "request.inflight")
    worst = 1.0
    for root in roots:
        kids = [sp for sp in spans if sp.parent is root]
        assert sorted(k.name for k in kids) == sorted(phase_names), kids
        for k in kids:                       # children nest inside the root
            assert root.t0 <= k.t0 and k.t1 <= root.t1 + 1e-9, (root, k)
        coverage = sum(k.duration_ms for k in kids) / root.duration_ms
        worst = min(worst, coverage)
    assert worst >= 0.90, f"phase coverage {worst:.1%} < 90%"

    s = tr.summary()
    agg = s["spans"]
    print(f"served {n_requests} requests in {len(BURSTS)} bursts "
          f"({wall_ms:.1f} ms wall)")
    print(f"phase coverage: every request's queued+staged+inflight spans "
          f"sum to >= {worst:.1%} of its wall-clock")
    for name in ("request.queued", "request.staged", "request.inflight",
                 "serve.launch", "exec.stream_gen", "exec.dispatch"):
        a = agg.get(name)
        if a:
            print(f"  {name:22s} x{a['count']:3d}  total {a['total_ms']:8.2f}"
                  f" ms  mean {a['mean_ms']:7.3f} ms")
    hit = s["metrics"]["counters"]
    print(f"counters: admitted {hit.get('serve.requests_admitted', 0)}, "
          f"batches {hit.get('serve.batches_launched', 0)}, "
          f"completed {hit.get('serve.requests_completed', 0)}")
    print(f"wrote {OUT} — load it at chrome://tracing or ui.perfetto.dev")


if __name__ == "__main__":
    main()
