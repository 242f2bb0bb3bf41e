"""SC matmul kernel benchmark: accuracy vs bitstream length + CPU-interpret
throughput, plus the analytic TPU cost note (DESIGN.md §6: on TPU the SC
path costs ~2*BL/32 VPU ops per MAC vs 1 MXU MAC — it is an approximation /
fault-tolerance feature, not a speed win; the paper's latency win is specific
to in-memory hardware).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sc_matmul import sc_matmul

from .common import fmt_table


def run(verbose=True, smoke=False) -> dict:
    key = jax.random.key(0)
    m, k, n = (8, 64, 16) if smoke else (32, 256, 64)
    a = jax.random.uniform(jax.random.key(1), (m, k))
    w = jax.random.uniform(jax.random.key(2), (k, n))
    exact = a @ w
    scale = float(jnp.abs(exact).mean())

    rows, results = [], {}
    for bl in ((32, 128) if smoke else (32, 64, 128, 256, 512)):
        t0 = time.time()
        approx = sc_matmul(a, w, bl, bm=8, bn=64, bk=64, interpret=True)
        approx.block_until_ready()
        dt = time.time() - t0
        err = float(jnp.abs(approx - exact).mean()) / scale
        pred_err = 1.0 / np.sqrt(bl * k) * np.sqrt(k) / 2 / scale  # ~p(1-p) bound
        results[bl] = {"rel_err": err, "seconds_interpret": dt}
        rows.append([bl, f"{100 * err:.2f}%", f"{dt:.2f}s",
                     f"{2 * bl / 32:.0f} VPU-ops/MAC"])
    if verbose:
        print(fmt_table(["BL", "rel.err", "CPU-interpret t", "TPU cost model"],
                        rows, title="\n== SC matmul kernel (popcount(AND) "
                                    "approximation of a 32x256 @ 256x64) =="))
        print("  err ~ 1/sqrt(BL): doubling BL halves variance "
              "(unipolar Bernoulli sampling).")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
