"""BankServer on a bursty LIT + KDE application mix.

Runnable entry point for the dynamic bank serving path (the counterpart of
examples/serve_lm.py for the SC stack):

    PYTHONPATH=src python examples/serve_sc.py

Bursts of local-image-thresholding windows (LIT, Eq. 5-6) and kernel-density
estimates (KDE, Eq. 10) arrive with shifting composition
(``repro.serve.apps.LIT_KDE_BURSTS``); the server buckets each burst into a
canonical padded bank template, so after the first occurrence of each mix
every burst reuses a warm BankPlan + jit program.  Every result is
bit-identical to a standalone ``appnet_stochastic`` call with the same
per-request key.
"""
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.apps import KDE_N
from repro.serve import BankServer
from repro.serve.apps import lit_kde_bursts

BL = 256


def main():
    enable_compile_cache()
    server = BankServer(max_slots=8, window_s=None)
    bursts = lit_kde_bursts(np.random.default_rng(0), jax.random.key(42), BL)

    print(f"serving {sum(map(len, bursts))} requests "
          f"in {len(bursts)} bursts (LIT 9x9 windows + KDE {KDE_N}-frame "
          f"histories, BL={BL})")
    for bi, burst in enumerate(bursts):
        t0 = time.perf_counter()
        results = server.serve([req for _, req, _ in burst])
        dt = (time.perf_counter() - t0) * 1e3
        line = []
        for (app, _, exact), out in zip(burst, results):
            got = float(np.mean([np.asarray(v) for v in out.values()]))
            line.append(f"{app.upper()} {got:.3f} (exact {exact:.3f})")
        n_lit = sum(app == "lit" for app, _, _ in burst)
        print(f"burst {bi}: {n_lit} LIT + {len(burst) - n_lit} KDE in "
              f"{dt:7.1f} ms   "
              + "; ".join(line[:3]) + (" ..." if len(line) > 3 else ""))

    s = server.stats()
    print(f"\nserved {s['n_requests']} requests in {s['n_batches']} batches: "
          f"bucket hit rate {s['bucket_hit_rate']:.0%}, "
          f"padding waste {s['padding_waste']:.0%}, "
          f"p50 {s['p50_ms']:.1f} ms, p99 {s['p99_ms']:.1f} ms, "
          f"{s['throughput_rps']:.0f} req/s steady-state")


if __name__ == "__main__":
    main()
