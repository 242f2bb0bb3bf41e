"""Fault campaign: accuracy under STT-MRAM fault models + chaos serving.

Two halves, one record (``BENCH_faults.json``):

* **Accuracy sweep** — extends Table 4's uniform-bitflip study to the full
  STT-MRAM fault taxonomy in ``core/faults.py``: each application's average
  output error (%) is swept over fault *rate* x fault *kind*:

    - ``transient``  — ``FaultModel(flip_rate=r)``: per-read random flips
      (retention/read-disturb upsets).  Bit-identical to the legacy
      ``bitflip_rate`` path at every rate.
    - ``stuck_at``   — ``FaultModel(stuck0_rate=r/2, stuck1_rate=r/2)``:
      manufacturing stuck-at cells, split evenly between SA0 and SA1.
    - ``dead_rows``  — ``FaultModel(dead_row_rate=r)``: whole word-line
      failures (a dead row zeroes one stream entirely).

  The sweep runs the *functional* app paths (``apps.*_stochastic``) where a
  checkpoint flip models one STT-MRAM array read, so each kind draws its
  masks per array exactly like the per-gate executor path does per gate.

* **Chaos serving trace** — replays an ``sc_multiply`` request trace through
  a ``BankServer`` whose ``fault_injector`` deterministically kills devices
  mid-run (rotating victim, periodic kill windows).  With retry + quarantine
  enabled the server must lose ZERO tickets, return bit-identical results to
  standalone execution, and keep p99 latency bounded; the clean-replay /
  chaos-replay time ratio is tracked as ``chaos_vs_clean_speedup`` so
  recovery overhead regressions surface in ``check_regression.py``.

Run standalone, the bench forces 4 host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) so quarantine has
somewhere to re-dispatch; imported in-process (benchmarks.run) it honors the
host's device count and skips the chaos half below 2 devices.

Output schema:
  {"bitstream_length", "rates", "kinds", "apps",
   "accuracy": {app: {kind: [err%, ...]}},
   "chaos": {"n_requests", "n_devices", "injected_failures", "retries",
             "quarantines", "redispatched_requests", "failed_tickets",
             "lost_tickets", "bit_identical", "p99_ms", "clean_s",
             "chaos_s", "chaos_vs_clean_speedup"} | None}
"""
from __future__ import annotations

import os
import sys

if "jax" not in sys.modules and \
        "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4").strip()

import dataclasses
import time

import jax
import numpy as np

from repro.core import apps, circuits, executor
from repro.core.faults import FaultModel

from .common import fmt_table, request_phases
from .table4_bitflip import _cases

RATES = (0.0, 0.05, 0.10, 0.15, 0.20)
SMOKE_RATES = (0.0, 0.10)
KINDS = ("transient", "stuck_at", "dead_rows")
BL = 256


def _model(kind: str, r: float) -> "FaultModel | None":
    """The swept FaultModel for one (kind, rate) cell; None = clean."""
    if r <= 0.0:
        return None
    if kind == "transient":
        return FaultModel(flip_rate=r)
    if kind == "stuck_at":
        return FaultModel(stuck0_rate=r / 2, stuck1_rate=r / 2)
    if kind == "dead_rows":
        return FaultModel(dead_row_rate=r)
    raise ValueError(f"unknown fault kind {kind!r}")


def accuracy_sweep(verbose: bool = True, smoke: bool = False) -> dict:
    """Average output error (%) per app x fault kind x rate."""
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    lit_a, ol_p, hdp_v, kde_x, kde_h = _cases(rng, smoke)
    # Smoke drops HDP: its Gaines-divider recurrence is the slowest app and
    # the three remaining apps already cover the >= 3-app acceptance bar.
    app_names = ("lit", "ol", "kde") if smoke else apps.APPS
    rates = SMOKE_RATES if smoke else RATES
    exact = {
        "lit": apps.lit_exact(lit_a),
        "ol": apps.ol_exact(ol_p),
        "hdp": apps.hdp_exact(hdp_v),
        "kde": apps.kde_exact(kde_x, kde_h),
    }

    def stoch(app, model):
        if app == "lit":
            return np.asarray(apps.lit_stochastic(key, lit_a, BL,
                                                  fault_model=model))
        if app == "ol":
            return np.asarray(apps.ol_stochastic(key, ol_p, BL,
                                                 fault_model=model))
        if app == "hdp":
            return np.asarray(apps.hdp_stochastic(key, hdp_v, BL,
                                                  fault_model=model))
        return np.asarray(apps.kde_stochastic(key, kde_x, kde_h, BL,
                                              fault_model=model))

    results, rows = {}, []
    for app in app_names:
        results[app] = {}
        for kind in KINDS:
            errs = [float(np.abs(stoch(app, _model(kind, r))
                                 - exact[app]).mean()) * 100
                    for r in rates]
            results[app][kind] = errs
            rows.append([app.upper(), kind] + [f"{e:.2f}" for e in errs])
    if verbose:
        hdr = ["App", "Kind"] + [f"@{int(r * 100)}%" for r in rates]
        print(fmt_table(hdr, rows,
                        title="\n== Fault campaign: avg output error (%) vs "
                              "fault rate x kind =="))
    return {"rates": list(rates), "kinds": list(KINDS),
            "apps": list(app_names), "by_app": results}


class ChaosInjector:
    """Deterministic rotating device killer for the serving trace.

    Counts batch launches; for each window of ``period`` launches one victim
    device is "down" — every launch placed on it fails.  The victim rotates
    each window, so every device dies at some point, accumulates the
    consecutive failures that trip the quarantine breaker, and must hand
    its in-flight work to the others.  Health probes (batch is None) always
    pass — a "device" recovers the moment its quarantine expires,
    exercising re-admission.
    """

    def __init__(self, devices, period: int = 6):
        self.dev_index = {d: i for i, d in enumerate(devices)}
        self.period = period
        self.launches = 0
        self.kills = 0

    def __call__(self, device, batch):
        if batch is None:                     # health probe: recovered
            return
        i = self.launches
        self.launches += 1
        victim = (i // self.period) % len(self.dev_index)
        if self.dev_index.get(device) == victim:
            self.kills += 1
            raise RuntimeError(f"chaos: injected device failure on {device}")


def _chaos_trace(n: int, bl: int, seed: int = 0):
    from repro.serve import circuit_request
    net = circuits.sc_multiply()
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.key(seed), n)
    return [circuit_request(net,
                            {"a": float(rng.uniform(0.1, 0.9)),
                             "b": float(rng.uniform(0.1, 0.9))},
                            keys[i], bl)
            for i in range(n)]


def _replay(server, reqs):
    """Submit the whole trace, drain, and account for every ticket."""
    t0 = time.perf_counter()
    tickets = [server.submit(r) for r in reqs]
    server.flush()
    outs, failed, lost = [], 0, 0
    for t in tickets:
        try:
            outs.append(t.result(timeout=60.0))
        except TimeoutError:                  # never resolved: a LOST ticket
            lost += 1
            outs.append(None)
        except Exception:                     # resolved, but with an error
            failed += 1
            outs.append(None)
    return time.perf_counter() - t0, outs, failed, lost


def _spot_check(outs, reqs, n: int = 8) -> bool:
    """Served (chaos-recovered) results vs standalone executor.run."""
    import jax.numpy as jnp
    idxs = np.linspace(0, len(reqs) - 1, n).astype(int)
    for i in idxs:
        if outs[i] is None:
            return False
        r = reqs[i]
        ref = executor.run(
            r, options=dataclasses.replace(r.options, decode=True))
        if not all(bool(jnp.array_equal(outs[i][k], ref[k])) for k in ref):
            return False
    return True


def _server(devices, injector=None):
    from repro.serve import BankServer
    return BankServer(max_slots=8, devices=devices, max_inflight=2,
                      placement="round_robin", max_retries=3,
                      retry_backoff_s=0.002, quarantine_after=2,
                      quarantine_s=0.02, fault_injector=injector)


def chaos_trace(verbose: bool = True, smoke: bool = False) -> "dict | None":
    devices = jax.devices()
    if len(devices) < 2:
        if verbose:
            print("\n[skip] chaos serving trace: only 1 jax device — run "
                  "`python -m benchmarks.fault_campaign` standalone to "
                  "force 4 host devices")
        return None
    n_requests = 24 if smoke else 96
    bl = 128 if smoke else 512
    reqs = _chaos_trace(n_requests, bl)
    reps = 1 if smoke else 3

    # Clean replay: identical server config, no injector.  Round-robin
    # placement rotates batches onto a different device offset each replay,
    # so warm up twice — enough rotations to land every batch shape on
    # every device before anything is timed.
    clean = _server(devices)
    _replay(clean, reqs)
    _replay(clean, reqs)
    clean_s = float("inf")
    for _ in range(reps):
        clean.reset_stats()
        s, _, _, _ = _replay(clean, reqs)
        clean_s = min(clean_s, s)
    # One extra traced replay (untimed): per-request queued/staged/inflight
    # attribution for the clean baseline.  Timed replays stay untraced.
    from repro.core import obs
    clean.trace = obs.Trace("fault-campaign-clean")
    _replay(clean, reqs)
    phases = request_phases(clean.stats())
    clean.trace = None
    clean.close()

    # Chaos replay: the injector rotates kills across all devices; retries
    # and quarantine re-dispatch must absorb every failure.
    chaos_s, stats, injector = float("inf"), None, None
    failed = lost = 0
    outs = []
    for _ in range(reps):
        inj = ChaosInjector(devices)
        srv = _server(devices, injector=inj)
        s, o, f, l = _replay(srv, reqs)
        st = srv.stats()
        srv.close()
        failed, lost = max(failed, f), max(lost, l)
        if s < chaos_s:
            chaos_s, stats, injector, outs = s, st, inj, o
    bit_identical = _spot_check(outs, reqs)

    res = {
        "n_requests": n_requests,
        "bitstream_length": bl,
        "n_devices": len(devices),
        "injected_failures": injector.kills,
        "retries": stats["retries"],
        "quarantines": stats["quarantines"],
        "redispatched_requests": stats["redispatched_requests"],
        "failed_tickets": failed,
        "lost_tickets": lost,
        "bit_identical": bool(bit_identical),
        "p99_ms": round(stats["p99_ms"], 3),
        "clean_s": round(clean_s, 4),
        "chaos_s": round(chaos_s, 4),
        "chaos_vs_clean_speedup": round(clean_s / chaos_s, 3),
        "phases": phases,
    }
    if verbose:
        print(f"\n== Chaos serving trace: {n_requests} requests, "
              f"{len(devices)} devices, BL={bl} ==")
        print(f"  injected failures : {injector.kills:4d}  "
              f"(retries {stats['retries']}, "
              f"quarantines {stats['quarantines']}, "
              f"re-dispatched {stats['redispatched_requests']})")
        print(f"  lost tickets      : {lost:4d}  (target: 0)")
        print(f"  failed tickets    : {failed:4d}  (target: 0)")
        print(f"  bit-identical     : {bit_identical}")
        print(f"  p99 latency       : {stats['p99_ms']:.1f} ms")
        print(f"  clean {clean_s:.3f} s vs chaos {chaos_s:.3f} s  "
              f"(recovery cost {chaos_s / clean_s:.2f}X)")
    return res


def run(verbose: bool = True, smoke: bool = False) -> dict:
    acc = accuracy_sweep(verbose=verbose, smoke=smoke)
    chaos = chaos_trace(verbose=verbose, smoke=smoke)
    return {
        "bitstream_length": BL,
        "rates": acc["rates"],
        "kinds": acc["kinds"],
        "apps": acc["apps"],
        "accuracy": acc["by_app"],
        "chaos": chaos,
    }


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    import json

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="reduced rates/apps/trace: CI-sized sanity pass")
    parser.add_argument("--out", default=None,
                        help="output path (default BENCH_faults.json; smoke "
                             "writes BENCH_faults_smoke.json)")
    args = parser.parse_args()
    out = args.out or ("BENCH_faults_smoke.json" if args.smoke
                       else "BENCH_faults.json")
    res = run(smoke=args.smoke)
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"wrote {out}")
