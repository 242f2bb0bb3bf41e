#!/usr/bin/env python3
"""Bring-up smoke run: the SC executor and BankServer on a TPU chip.

    python chip_smoke.py               # batch + served phases on one chip
    python chip_smoke.py --chips 4     # only the four-chip served phase

Runs in one process and never falls back to the CPU: if JAX's first device
is not a TPU it exits non-zero before any phase.  Phases:

* **batch** — the four Table-3 app netlists (LIT, OL, HDP, KDE) through
  ``executor.run`` at BL=1024: LIT and KDE over a 320x240 frame (76,800
  pixels), OL over the paper's 64x64 grid (256 tiles of 16 pixels), HDP over
  4,096 queries.  Each runs under ``compiled``, ``compiled_pallas`` and
  ``compiled_megakernel``; every output must be bit-identical to the
  gate-by-gate ``reference`` backend with the same key (traced into one
  program so the frame fits the device), and the decoded values must lie
  within ``TOLERANCE`` (mean absolute error) of the exact float app.  The
  megakernel must have lowered (``megakernel.lowered`` counted, no
  ``megakernel.declined``).  On the chip each backend's program must hold
  its named Pallas kernels (``KERNELS``), and its compiled memory analysis
  is printed next to ``peak_bytes_in_use``.
* **served** — ``BankServer(max_retries=0)`` on the bursty LIT + KDE trace
  of ``examples/serve_sc.py`` at BL=1024; every ticket must equal a
  standalone ``executor.run`` of its request bit for bit, with no failed or
  shed ticket, no retry and no quarantine.
* **four-chip** (``--chips 4`` only) — the same trace served over every
  device and over the first device alone, in one process; per-ticket
  results must be bit-identical and batches must land on more than one
  device.

Each phase prints its own lines (cold = first call, compile included; warm
= second call; ``peak_bytes_in_use`` of device 0).  These are bring-up
facts, not benchmark numbers.  The last line is the JSON verdict
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

try:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    from repro.core import apps, bitstream as bs, executor, obs
    from repro.core.appnet import APP_NETLISTS
    from repro.serve import BankServer
    from repro.serve.apps import LIT_KDE_BURSTS, lit_kde_bursts
except ImportError as e:                        # script copied out alone
    sys.exit(f"chip_smoke: cannot import the repro package from "
             f"{ROOT / 'src'}: {e}")

BL = 1024
FRAME = (240, 320)          # quarter VGA: 76,800 pixels
OL_TILES = 256              # 64x64 grid, 16 pixels per OL circuit
HDP_QUERIES = 4096
BACKENDS = ("compiled", "compiled_pallas", "compiled_megakernel")
#: The Pallas kernels (``pallas_call`` names) each backend's program must hold
#: on the chip: the SNG and per-pass kernels, or the whole-plan megakernel
#: (whose streams come from the jnp SNG).
KERNELS = {"compiled": (), "compiled_pallas": ("sng_words", "packed_logic"),
           "compiled_megakernel": ("plan_megakernel",)}
#: Mean |decoded - exact| bound per app at BL=1024.  SC sampling noise alone
#: is at most 0.5/sqrt(1024) ~= 0.016 per value; the rest is each composed
#: circuit's own bias.  Measured on 4,096 values (two seeds, compiled
#: backend, CPU): LIT 0.062 (bias +0.054 from the XOR variance and NAND
#: square root), OL 0.011, HDP 0.028, KDE 0.074 (bias +0.073 from the
#: truncated Maclaurin ladder).
TOLERANCE = {"lit": 0.08, "ol": 0.02, "hdp": 0.05, "kde": 0.10}


class SmokeFailure(Exception):
    """A phase's check failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> "int | None":
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def app_inputs(app: str, rng: np.random.Generator, frame=FRAME,
               ol_tiles: int = OL_TILES, hdp_queries: int = HDP_QUERIES):
    """App-level inputs (``apps.appnet_inputs`` keywords) and exact values.

    LIT thresholds every pixel of a random ``frame`` over its 9x9 window
    (edge-padded); KDE scores every pixel against ``KDE_N`` noisy earlier
    frames; OL multiplies six probabilities per pixel; HDP answers
    independent queries.
    """
    h, w = frame
    if app == "lit":
        img = rng.uniform(0.05, 0.95, size=frame).astype(np.float32)
        pad = np.pad(img, apps.WINDOW // 2, mode="edge")
        win = np.lib.stride_tricks.sliding_window_view(
            pad, (apps.WINDOW, apps.WINDOW))
        a = np.ascontiguousarray(win.reshape(h * w, apps.WINDOW ** 2))
        return {"a": a}, apps.lit_exact(a)
    if app == "kde":
        x_t = rng.uniform(0.1, 0.9, size=h * w).astype(np.float32)
        hist = np.clip(x_t[:, None] + rng.normal(0.0, 0.15, (h * w, apps.KDE_N)),
                       0.0, 1.0).astype(np.float32)
        return {"x_t": x_t, "hist": hist}, apps.kde_exact(x_t, hist)
    if app == "ol":
        p = rng.uniform(0.6, 1.0, size=(ol_tiles, 16, 6)).astype(np.float32)
        return {"p": p}, apps.ol_exact(p)
    if app == "hdp":
        v = {k: rng.uniform(0.1, 0.9, size=hdp_queries).astype(np.float32)
             for k in apps.HDP_KEYS}
        return {"v": v}, apps.hdp_exact(v)
    raise KeyError(app)


def _decode(app: str, outs: dict) -> np.ndarray:
    """Decoded app value per element, laid out like the exact app's."""
    vals = [np.asarray(bs.to_value(outs[o], BL)) for o in sorted(outs)]
    if app == "ol":                     # 16 pixel outputs per tile
        return np.stack(vals, axis=-1)
    (v,) = vals
    return v


def _outputs_in_order(net, outs: dict) -> dict:
    # OL's 16 outputs sort by name; the exact app orders pixels 0..15.
    return {f"{i:02d}": outs[o] for i, o in enumerate(net.outputs)}


def _counters() -> dict:
    return obs.REGISTRY.snapshot()["counters"]


def run_batch_app(app: str, inputs: dict, exact: np.ndarray, key, *,
                  interpret: "bool | None" = None,
                  on_chip: bool = True) -> dict:
    """One app through every compiled backend against the reference.

    Raises ``SmokeFailure`` on any mismatch; returns per-backend facts.
    """
    net = APP_NETLISTS[app]()
    values = apps.appnet_inputs(app, **inputs)
    n = int(np.size(exact))

    def request(backend, vals, k):
        return executor.ExecRequest(net, vals, k, executor.ExecOptions(
            backend=backend, bitstream_length=BL, interpret=interpret))

    # The reference interprets gate by gate; traced into one program, XLA
    # frees each node once dead, so a whole frame fits on the device.
    t0 = time.perf_counter()
    ref = jax.block_until_ready(jax.jit(
        lambda v, k: executor.run(request("reference", v, k)))(values, key))
    log(f"  {app}: reference {n} values in {time.perf_counter() - t0:.3f} s "
        f"(gate-by-gate interpreter, one program, compile included)")
    report = {}
    for backend in BACKENDS:
        req = request(backend, values, key)
        before = _counters()
        t0 = time.perf_counter()
        out = jax.block_until_ready(executor.run(req))
        cold = time.perf_counter() - t0
        after = _counters()
        t0 = time.perf_counter()
        jax.block_until_ready(executor.run(req))
        warm = time.perf_counter() - t0
        same = all(bool(jnp.array_equal(out[o], ref[o])) for o in ref)
        check(same, f"{app}/{backend}: output bits differ from reference")
        if backend == "compiled_megakernel":
            lowered = after.get("megakernel.lowered", 0) \
                - before.get("megakernel.lowered", 0)
            declined = after.get("megakernel.declined", 0) \
                - before.get("megakernel.declined", 0)
            check(lowered > 0 and declined == 0,
                  f"{app}: megakernel lowered {lowered}x, declined "
                  f"{declined}x: the per-pass path ran instead")
        kernels = mem = None
        if on_chip:
            # The same program, lowered and compiled ahead of time: its text
            # names the kernels it holds, its analysis the HBM it plans for.
            lowered = jax.jit(lambda v, k: executor.run(
                request(backend, v, k))).lower(values, key)
            text = lowered.as_text()
            kernels = [n for n in KERNELS[backend] if n in text]
            check(len(kernels) == len(KERNELS[backend]),
                  f"{app}/{backend}: program holds {kernels} of the Pallas "
                  f"kernels {list(KERNELS[backend])}")
            ma = lowered.compile().memory_analysis()
            mem = {"temp": ma.temp_size_in_bytes,
                   "argument": ma.argument_size_in_bytes,
                   "output": ma.output_size_in_bytes}
        got = _decode(app, _outputs_in_order(net, out))
        mae = float(np.mean(np.abs(got - exact)))
        check(mae <= TOLERANCE[app],
              f"{app}/{backend}: mean |decoded - exact| {mae:.4f} > "
              f"{TOLERANCE[app]}")
        report[backend] = {"cold_s": cold, "warm_s": warm, "mae": mae,
                           "kernels": kernels, "compiled_bytes": mem,
                           "peak_bytes": peak_bytes()}
        log(f"  {app}/{backend}: bit-identical to reference, "
            f"cold {cold:.3f} s, warm {warm:.4f} s, "
            f"mean |decoded - exact| {mae:.4f} (<= {TOLERANCE[app]}), "
            f"Pallas kernels {kernels}, compiled bytes {mem}, "
            f"peak_bytes_in_use {report[backend]['peak_bytes']}")
    return report


def phase_batch(seed: int) -> dict:
    log(f"phase batch: BL={BL}, LIT/KDE frame {FRAME[1]}x{FRAME[0]} "
        f"({FRAME[0] * FRAME[1]} pixels), OL {OL_TILES} tiles x 16 px, "
        f"HDP {HDP_QUERIES} queries")
    rng = np.random.default_rng(seed)
    key = jax.random.key(seed)
    out = {}
    for i, app in enumerate(("lit", "ol", "hdp", "kde")):
        inputs, exact = app_inputs(app, rng)
        out[app] = run_batch_app(app, inputs, exact,
                                 jax.random.fold_in(key, i))
    return out


def serve_trace(bursts, devices) -> "tuple[list, dict]":
    """Serve every burst of ``bursts`` (``lit_kde_bursts`` output) on one
    ``BankServer`` over ``devices``; returns (results, stats).

    Bursts are submitted back to back and the tickets collected at the end,
    so several batches are in flight at once.  Any failed ticket fails the
    phase (after counting them all).
    """
    server = BankServer(max_slots=8, window_s=None, devices=devices,
                        max_retries=0, decode=False)
    tickets = []
    for burst in bursts:
        tickets += [server.submit(req) for _, req, _ in burst]
        server.flush()
    server.close()                      # drains: every ticket settles
    stats = server.stats()
    _check_clean(stats)
    return [t.result() for t in tickets], stats


def _check_clean(stats: dict) -> None:
    for k in ("failed_requests", "shed_requests", "retries", "quarantines",
              "redispatched_requests", "deadline_exceeded"):
        check(stats[k] == 0, f"served: {k} = {stats[k]}")


def _same_tree(a: dict, b: dict) -> bool:
    return sorted(a) == sorted(b) and \
        all(bool(jnp.array_equal(a[k], b[k])) for k in a)


def phase_served(seed: int, *, bursts=LIT_KDE_BURSTS) -> dict:
    bursts = lit_kde_bursts(np.random.default_rng(seed),
                            jax.random.key(seed), BL, bursts)
    devices = jax.devices()[:1]
    n = sum(map(len, bursts))
    log(f"phase served: {n} LIT+KDE requests in {len(bursts)} bursts, "
        f"BL={BL}, {len(devices)} device(s)")
    t0 = time.perf_counter()
    results, stats = serve_trace(bursts, devices)
    wall = time.perf_counter() - t0
    reqs = [(app, req, exact) for burst in bursts for app, req, exact in burst]
    errs = []
    for (app, req, exact), got in zip(reqs, results):
        alone = executor.run(req)
        check(_same_tree(got, alone),
              f"served {app} ticket differs from a standalone run()")
        errs.append(abs(float(np.mean(_decode(app, got))) - exact))
    mae = float(np.mean(errs))
    log(f"  served: {n} tickets bit-identical to standalone run(), "
        f"{stats['n_batches']} batches, failed {stats['failed_requests']}, "
        f"shed {stats['shed_requests']}, retries {stats['retries']}, "
        f"quarantines {stats['quarantines']}, wall {wall:.3f} s "
        f"(compile included), mean |decoded - exact| {mae:.4f}, "
        f"peak_bytes_in_use {peak_bytes()}")
    return {"stats": stats, "wall_s": wall}


def phase_four_chip(seed: int) -> dict:
    devices = jax.devices()
    check(len(devices) > 1, f"four-chip phase needs >1 device: {devices}")
    bursts = lit_kde_bursts(np.random.default_rng(seed),
                            jax.random.key(seed), BL)
    log(f"phase four-chip: {sum(map(len, bursts))} LIT+KDE requests, "
        f"BL={BL}, {len(devices)} devices vs device 0 alone")
    one, _ = serve_trace(bursts, devices[:1])
    many, st_many = serve_trace(bursts, devices)
    for a, b in zip(one, many):
        check(_same_tree(a, b), "sharded ticket differs from one-device run")
    per_dev = {d["device"]: d["n_batches"] for d in st_many["devices"]}
    used = sum(1 for v in per_dev.values() if v > 0)
    log(f"  four-chip: {len(one)} tickets bit-identical across placements; "
        f"batches per device {per_dev}")
    check(used > 1, f"batches landed on {used} device(s) only: {per_dev}")
    return {"batches_per_device": per_dev}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip served phase")
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)} jax={jax.__version__} compile_cache={cache}")
    if d0.platform != "tpu":
        log(f"chip_smoke: no TPU chip found (JAX's first device is a "
            f"{d0.platform!r} device); this run needs a TPU and does not "
            "fall back to the CPU")
        return 2
    if args.chips == 4:
        if len(devices) < 4:
            log(f"chip_smoke: --chips 4 needs four TPU chips, found "
                f"{len(devices)}")
            return 2
        phase_four_chip(args.seed)
    else:
        phase_batch(args.seed)
        phase_served(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
