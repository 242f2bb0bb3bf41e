"""Liveness-driven streaming execution benchmark (BENCH_megakernel.json).

Two views of the PR-9 memory-model work:

* **Peak-live words** — for each Table-3 application's merged cost-stage
  bank, the naive executor keeps every PI and intermediate alive at the
  full stream width (``naive_live`` x W words), while the liveness-
  allocated plan holds at most ``max_live`` buffers and the word-tiled
  streamer (``ExecOptions.word_chunk``) narrows each to one chunk of
  words.  The tracked ratio is ``naive_live * W / (max_live * chunk)``
  — the KDE bank is the acceptance headline (>= 4X at BL=16384).  The
  same ``max_live`` sizes the whole-plan megakernel's VMEM scratch pool
  and is priced as subarray occupancy by ``arch.evaluate_bank_plan``.

* **Wall clock** — the KDE application netlist (932 gates, combinational)
  with a batch dim at BL=16384, chunked-streamed vs the one-shot per-pass
  jnp path.  At full width every live buffer is batch x 512 words and the
  working set falls out of cache; streaming at the auto-tuned chunk keeps
  it resident (acceptance: >= 1.3X on CPU).  Both paths are bit-identical
  (also asserted here on the decoded outputs).

Smoke sizes (BL=2048, batch=8) fit CI but sit near 1X by design — the
cache win needs paper-scale working sets — so check_regression.py gives
this record a collapse-only tolerance.

Output schema (written here and by benchmarks/run.py):
  {"bitstream_lengths", "stream_chunk", "banks": {app: {"members",
   "max_live", "naive_live", "live_reduction", "live_occupancy_frac",
   "peak_live_words": {bl: {"naive", "streamed", "reduction"}}}},
   "wallclock": {"app", "bitstream_length", "batch", "word_chunk",
   "unchunked_ms", "chunked_ms", "chunked_speedup", "bit_identical"}}
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core import apps, arch, bitstream as bs, executor
from repro.core.appnet import APP_NETLISTS
from repro.core.plan import compile_bank_plan, compile_plan

from .common import fmt_table, time_ms

#: Streaming chunk (words) used for the peak-live-words table: the
#: wall-clock auto-tune below lands on 64 at paper scale, and 64 words x
#: 32 bits is two VREG lanes' worth per live row — clamped to W when a
#: small BL has fewer words than that.
STREAM_CHUNK = 64


def _bank_record(app: str, bls, chunk_cap: int) -> dict:
    bank = compile_bank_plan(apps.cost_stage_netlists(app))
    cost = arch.evaluate_bank_plan(bank, arch.StochIMCConfig())
    peak = {}
    for bl in bls:
        w = bs.n_words(bl)
        chunk = min(chunk_cap, w)
        naive = cost.naive_live * w
        streamed = cost.max_live * chunk
        peak[str(bl)] = {"naive": naive, "streamed": streamed,
                         "reduction": round(naive / max(streamed, 1), 2)}
    return {"members": cost.n_members,
            "max_live": cost.max_live, "naive_live": cost.naive_live,
            "live_reduction": round(cost.live_reduction, 2),
            "live_occupancy_frac": round(cost.live_occupancy_frac, 4),
            "peak_live_words": peak}


def _wallclock(bl: int, batch: int, chunks, iters: int) -> dict:
    net = APP_NETLISTS["kde"]()
    rng = np.random.default_rng(0)
    vals = apps.appnet_inputs(
        "kde", x_t=rng.uniform(0.2, 0.8, (batch,)).astype(np.float32),
        hist=rng.uniform(0.1, 0.9, (batch, 8)).astype(np.float32))
    key = jax.random.key(0)

    def run(chunk):
        opts = executor.ExecOptions(bitstream_length=bl, decode=True,
                                    word_chunk=chunk)
        return executor.run(executor.ExecRequest(net, vals, key, opts))

    base_out = run(None)
    base_ms = time_ms(lambda: run(None), iters)
    best = None
    for ch in chunks:
        ms = time_ms(lambda: run(ch), iters)
        if best is None or ms < best[1]:
            best = (ch, ms)
    chunk, chunked_ms = best
    chunk_out = run(chunk)
    identical = all(bool((chunk_out[k] == base_out[k]).all())
                    for k in base_out)
    # Phase breakdown: the unchunked run's stream-generation phase on its
    # own jitted entry; the chunked scan interleaves gen with passes, so
    # only the unchunked split is separable.
    plan = compile_plan(net)
    gen_fn = jax.jit(lambda k: executor._gen_pi_streams(
        tuple(plan.pis), vals, k, bl))
    gen_ms = time_ms(lambda: gen_fn(key), iters)
    phases = {"gen_ms": round(gen_ms, 3),
              "pass_ms": round(max(base_ms - gen_ms, 0.0), 3),
              "total_ms": round(base_ms, 3)}
    return {"app": "kde_appnet", "bitstream_length": bl, "batch": batch,
            "word_chunk": chunk,
            "unchunked_ms": round(base_ms, 3),
            "chunked_ms": round(chunked_ms, 3),
            "chunked_speedup": round(base_ms / chunked_ms, 2),
            "phases": phases,
            "bit_identical": identical}


def run(verbose: bool = True, smoke: bool = False) -> dict:
    bls = (512, 2048) if smoke else (1024, 4096, 16384)
    banks = {app: _bank_record(app, bls, STREAM_CHUNK) for app in apps.APPS}
    wc = (_wallclock(2048, 8, (16, 32), iters=3) if smoke
          else _wallclock(16384, 32, (32, 64, 128), iters=10))

    results = {"bitstream_lengths": list(bls), "stream_chunk": STREAM_CHUNK,
               "banks": banks, "wallclock": wc}
    if verbose:
        bl_hi = str(bls[-1])
        rows = [[app.upper(), r["members"], r["naive_live"], r["max_live"],
                 f"{r['live_reduction']:.2f}X",
                 f"{r['peak_live_words'][bl_hi]['reduction']:.1f}X"]
                for app, r in banks.items()]
        print(fmt_table(
            ["Bank", "Members", "NaiveLive", "MaxLive", "BufReuse",
             f"PeakWords@{bl_hi}"],
            rows, title=f"\n== Megakernel bench: liveness-allocated "
                        f"streaming (chunk={STREAM_CHUNK} words) =="))
        print(f"\n  KDE wall-clock @ BL={wc['bitstream_length']} "
              f"batch={wc['batch']}: unchunked {wc['unchunked_ms']:.1f} ms "
              f"-> chunked {wc['chunked_ms']:.1f} ms "
              f"(chunk={wc['word_chunk']}, {wc['chunked_speedup']:.1f}X, "
              f"bit_identical={wc['bit_identical']})"
              + ("" if smoke else "  (target: >= 1.3X)"))
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    import json

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="tiny BL/batch: CI-sized sanity pass")
    parser.add_argument("--out", default=None,
                        help="output path (default BENCH_megakernel.json; "
                             "smoke writes BENCH_megakernel_smoke.json)")
    args = parser.parse_args()
    out = args.out or ("BENCH_megakernel_smoke.json" if args.smoke
                       else "BENCH_megakernel.json")
    res = run(smoke=args.smoke)
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"wrote {out}")
