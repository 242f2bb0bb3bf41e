"""Fig. 11 — lifetime improvement (Eq. 11 over utilized cells) of Stoch-IMC
and [22] relative to binary IMC, per application.
"""
from __future__ import annotations

from repro.core import apps

from . import table3_apps
from .common import fmt_table, geomean


def run(verbose=True) -> dict:
    t3 = table3_apps.run(verbose=False)
    results = {}
    rows = []
    for app in apps.APPS:
        lt = t3["apps"][app]["lifetime"]
        ours = lt["stoch"] / lt["binary"]
        cram = lt["cram"] / lt["binary"]
        results[app] = {"stoch_vs_binary": ours, "cram_vs_binary": cram,
                        "stoch_vs_cram": ours / cram}
        rows.append([app.upper(), f"{cram:.4f}X", f"{ours:.2f}X",
                     f"{ours / cram:.1f}X"])
    g_ours = geomean([r["stoch_vs_binary"] for r in results.values()])
    g_vs_cram = geomean([r["stoch_vs_cram"] for r in results.values()])
    if verbose:
        print(fmt_table(["App", "[22] vs binary", "Stoch-IMC vs binary",
                         "Stoch-IMC vs [22]"], rows,
                        title="\n== Fig. 11: lifetime improvement (Eq. 11) =="))
        print(f"\n  Geomean lifetime vs binary: {g_ours:.1f}X (paper: 4.9X); "
              f"vs [22]: {g_vs_cram:.1f}X (paper: 216.3X)")
    return {"apps": results, "geomean_vs_binary": g_ours,
            "geomean_vs_cram": g_vs_cram}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
