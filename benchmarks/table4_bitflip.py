"""Table 4 — average output error (%) under injected bitflips, binary-IMC
(8-bit) vs Stoch-IMC (256-bit), across the four applications.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core import apps

from .common import fmt_table

RATES = (0.0, 0.05, 0.10, 0.15, 0.20)
BL = 256

PAPER_STOCH_20 = {"lit": 6.4, "ol": 0.18, "hdp": 0.13, "kde": 1.53}


def _cases(rng, smoke=False):
    n = 2 if smoke else 1       # smoke: halve batch sizes, keep BL/rates
    lit_a = rng.random((48 // n, 81))
    ol_p = rng.random((128 // n, 6)) * 0.5 + 0.5
    # HDP keeps its full batch: its divider error sits closest to the 10%
    # validation bound and needs the sample size to stay below it.
    hdp_v = {k: rng.random(64) * 0.8 + 0.1 for k in apps.HDP_KEYS}
    kde_x = rng.random(16 // n)
    kde_h = rng.random((16 // n, apps.KDE_N))
    return lit_a, ol_p, hdp_v, kde_x, kde_h


def run(verbose=True, smoke=False) -> dict:
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    lit_a, ol_p, hdp_v, kde_x, kde_h = _cases(rng, smoke)
    exact = {
        "lit": apps.lit_exact(lit_a),
        "ol": apps.ol_exact(ol_p),
        "hdp": apps.hdp_exact(hdp_v),
        "kde": apps.kde_exact(kde_x, kde_h),
    }

    def stoch(app, rate):
        if app == "lit":
            return np.asarray(apps.lit_stochastic(key, lit_a, BL, rate))
        if app == "ol":
            return np.asarray(apps.ol_stochastic(key, ol_p, BL, rate))
        if app == "hdp":
            return np.asarray(apps.hdp_stochastic(key, hdp_v, BL, rate))
        return np.asarray(apps.kde_stochastic(key, kde_x, kde_h, BL, rate))

    def binary(app, rate):
        r = np.random.default_rng(1)
        if app == "lit":
            return apps.lit_binary8(r, lit_a, rate)
        if app == "ol":
            return apps.ol_binary8(r, ol_p, rate)
        if app == "hdp":
            return apps.hdp_binary8(r, hdp_v, rate)
        return apps.kde_binary8(r, kde_x, kde_h, rate)

    results = {}
    rows = []
    for app in apps.APPS:
        b_err = [float(np.abs(binary(app, r) - exact[app]).mean()) * 100
                 for r in RATES]
        s_err = [float(np.abs(stoch(app, r) - exact[app]).mean()) * 100
                 for r in RATES]
        results[app] = {"binary": b_err, "stoch": s_err,
                        "paper_stoch_20": PAPER_STOCH_20[app]}
        rows.append([app.upper()] + [f"{e:.1f}" for e in b_err]
                    + [f"{e:.2f}" for e in s_err])
    if verbose:
        hdr = (["App"] + [f"bin@{int(r*100)}%" for r in RATES]
               + [f"sc@{int(r*100)}%" for r in RATES])
        print(fmt_table(hdr, rows,
                        title="\n== Table 4: avg output error (%) vs injected "
                              "bitflip rate =="))
        worst = max(results[a]["stoch"][-1] for a in apps.APPS)
        print(f"\n  Stoch-IMC worst error @20% flips: {worst:.2f}% "
              f"(paper: <6.5% across apps)")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
