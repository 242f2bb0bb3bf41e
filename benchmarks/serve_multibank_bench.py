"""Multi-bank async serving vs the single-bank sync server (BENCH_serve_multibank.json).

Replays a 64-request bursty LIT + KDE application trace (the
examples/serve_sc.py traffic shape: burst composition shifts and revisits)
through two server configurations:

  * **single_bank** — the PR-4 serving model, expressed as
    ``BankServer(devices=[d0], max_inflight=0)`` driven burst-by-burst with
    ``serve()``: every burst forms one padded bank, dispatches to the one
    device, and blocks on its results before the next burst is admitted.
  * **multibank_async** — the full engine: requests stream in across burst
    boundaries (``submit`` only), so admission overlaps in-flight execution
    (JAX async dispatch, ``max_inflight`` batches per device), batches fill
    to ``max_slots`` across bursts (continuous batching widens each bank and
    eliminates padding for this trace), and staged banks shard round-robin
    over every available device.

Run standalone, the bench forces 4 host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) so the sharded path
is exercised on CPU; imported in-process (benchmarks.run) it uses however
many devices the host already has and the runner skips it when only one
exists.

Acceptance (ISSUE 6): multibank_async sustains >= 2X the steady-state
throughput of single_bank on the 64-request trace, and a spot check of
served results is bit-identical to standalone ``executor.execute_value``
with the same per-request key (full per-request identity is pinned by
tests/test_serve_multibank.py).

Output schema:
  {"bitstream_length", "n_requests", "n_bursts", "n_devices",
   "max_slots_async", "max_slots_single", "bit_identical",
   "single_bank_s", "multibank_s", "single_bank_rps", "multibank_rps",
   "speedup_vs_single_bank", "single_bank": {...stats...},
   "multibank": {...stats...}}
"""
from __future__ import annotations

import os
import sys

if "jax" not in sys.modules and \
        "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4").strip()

import time

import jax
import numpy as np

from repro.core import executor
from repro.core.apps import KDE_N
from repro.serve import BankServer, app_request

from .common import request_phases

# Four bursts of (n_lit, n_kde) sum to 8 LIT + 8 KDE: each 16-request
# admission window packs one power-of-two bank with zero padding, so the
# async server's continuous batching gets full credit for widening banks.
BURST_PATTERN = [(3, 1), (1, 3), (2, 2), (2, 2)]


def build_trace(n_requests: int, bl: int, seed: int = 0):
    """``[(burst of SCRequest, ...), ...]`` plus flat (net, values, key) refs."""
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.key(seed), n_requests)
    bursts, refs = [], []
    ki = 0
    bi = 0
    while ki < n_requests:
        n_lit, n_kde = BURST_PATTERN[bi % len(BURST_PATTERN)]
        burst = []
        for is_kde in [False] * n_lit + [True] * n_kde:
            if ki >= n_requests:
                break
            if is_kde:
                x_t = float(rng.uniform(0.2, 0.8))
                hist = rng.uniform(0.2, 0.8, size=(KDE_N,))
                req = app_request("kde", keys[ki], bl, x_t=x_t, hist=hist)
            else:
                a = rng.uniform(0.1, 0.9, size=(81,))
                req = app_request("lit", keys[ki], bl, a=a)
            burst.append(req)
            refs.append(req)
            ki += 1
        bursts.append(burst)
        bi += 1
    return bursts, refs


def _replay_single(server: BankServer, bursts) -> float:
    """PR-4 drive: serve (and block on) each burst before the next arrives."""
    t0 = time.perf_counter()
    for burst in bursts:
        server.serve(burst)
    return time.perf_counter() - t0


def _replay_async(server: BankServer, bursts) -> tuple:
    """Stream every burst through submit(); wait only at the very end."""
    t0 = time.perf_counter()
    tickets = [server.submit(r) for burst in bursts for r in burst]
    server.flush()
    outs = [t.result() for t in tickets]
    return time.perf_counter() - t0, outs


def _spot_check(outs, refs, n: int = 8) -> bool:
    """Served results vs standalone execute_value for ``n`` spread requests."""
    import jax.numpy as jnp
    idxs = np.linspace(0, len(refs) - 1, n).astype(int)
    for i in idxs:
        r = refs[i]
        ref = executor.execute_value(r.net, r.values, r.key,
                                     r.bitstream_length)
        got = outs[i]
        if not all(bool(jnp.array_equal(got[k], ref[k])) for k in ref):
            return False
    return True


def run(verbose: bool = True, smoke: bool = False) -> dict:
    # Full size uses a long bitstream so per-batch execution dominates the
    # (linear, width-independent) host-side argument processing: that is the
    # regime the bank-level batching targets.  Smoke stays tiny for CI —
    # host overheads then dominate both servers and the smoke speedup ratio
    # sits far below the committed one (check_regression tolerance covers
    # the gap).
    bl = 128 if smoke else 2048
    n_requests = 24 if smoke else 64
    devices = jax.devices()
    bursts, refs = build_trace(n_requests, bl)
    reps = 1 if smoke else 5

    # Single-bank sync baseline: one device, block per batch, per-burst
    # admission (PR-4 defaults: max_slots=8, padded templates).
    single = BankServer(max_slots=8, devices=[devices[0]], max_inflight=0)
    _replay_single(single, bursts)              # warmup: compile + trace
    single_s, single_stats = float("inf"), None
    for _ in range(reps):
        single.reset_stats()
        s = _replay_single(single, bursts)
        if s < single_s:
            single_s, single_stats = s, single.stats()

    # Multi-bank async server: all devices, overlapped admission, wide banks.
    # Affinity placement keeps repeat layouts on jit-warm devices and spills
    # to a cold one only when the warm set is saturated — placement is then
    # deterministic across reps, so one warmup replay warms every device the
    # steady state touches (round_robin would rotate onto cold devices).
    multi = BankServer(max_slots=16, devices=devices, max_inflight=4,
                       placement="affinity")
    _, outs = _replay_async(multi, bursts)      # warmup
    bit_identical = _spot_check(outs, refs)
    multi_s, multi_stats = float("inf"), None
    for _ in range(reps):
        multi.reset_stats()
        s, outs = _replay_async(multi, bursts)
        if s < multi_s:
            multi_s, multi_stats = s, multi.stats()

    # One extra traced replay (untimed) for the per-request phase breakdown
    # (queued/staged/inflight histograms).  Timed replays stay untraced.
    from repro.core import obs
    multi.trace = obs.Trace("serve-multibank-bench")
    _replay_async(multi, bursts)
    phases = request_phases(multi.stats())
    multi.trace = None

    results = {
        "bitstream_length": bl,
        "n_requests": n_requests,
        "n_bursts": len(bursts),
        "n_devices": len(devices),
        "max_slots_async": multi.max_slots,
        "max_slots_single": single.max_slots,
        "bit_identical": bool(bit_identical),
        "single_bank_s": round(single_s, 4),
        "multibank_s": round(multi_s, 4),
        "single_bank_rps": round(n_requests / single_s, 2),
        "multibank_rps": round(n_requests / multi_s, 2),
        "speedup_vs_single_bank": round(single_s / multi_s, 2),
        "single_bank": {k: round(v, 4) if isinstance(v, float) else v
                        for k, v in single_stats.items()
                        if not isinstance(v, list)},
        "multibank": {k: round(v, 4) if isinstance(v, float) else v
                      for k, v in multi_stats.items()
                      if not isinstance(v, list)},
        "multibank_devices": multi_stats["devices"],
        "phases": phases,
    }
    if verbose:
        print(f"\n== Multi-bank serve bench: {n_requests} requests, "
              f"{len(bursts)} bursts, {len(devices)} device(s), BL={bl} ==")
        print(f"  single-bank sync : {single_s:8.3f} s  "
              f"({results['single_bank_rps']:8.1f} req/s, "
              f"{single_stats['n_batches']} batches, "
              f"padding waste {single_stats['padding_waste']:.0%})")
        print(f"  multi-bank async : {multi_s:8.3f} s  "
              f"({results['multibank_rps']:8.1f} req/s, "
              f"{multi_stats['n_batches']} batches, "
              f"padding waste {multi_stats['padding_waste']:.0%}, "
              f"bit-identical: {bit_identical})")
        print(f"  speedup vs single-bank server: "
              f"{results['speedup_vs_single_bank']:.1f}X  (target: >= 2X)")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    import json

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="tiny BL/trace: CI-sized sanity pass")
    parser.add_argument("--out", default=None,
                        help="output path (default BENCH_serve_multibank.json;"
                             " smoke writes BENCH_serve_multibank_smoke.json)")
    args = parser.parse_args()
    out = args.out or ("BENCH_serve_multibank_smoke.json" if args.smoke
                       else "BENCH_serve_multibank.json")
    res = run(smoke=args.smoke)
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"wrote {out}")
