"""KDE: kernel density estimation of a pixel against N=8 earlier frames
(paper sec. 5.3, Eq. 10), its inputs, its plain stochastic reference and its
exact value.

The reference evaluates the paper's KDE circuit (Fig. 9(d)) on packed
streams with the key discipline of ``sc.py``.  Each history term ``i`` is
five factors ``e^{-0.8 |x_t - h_i|}``; factor ``(i, f)`` draws a correlated
pair ``(x_t, h_i)`` on lane ``5i + f`` (XOR gives ``|x_t - h_i|``) and a
fifth-order Maclaurin ladder over constants ``0.8 / k``, ``k = 1..5``, on
lanes ``40 + 5 (5i + f) + k - 1``.  The eight terms are averaged by a MUX
tree whose seven value-0.5 selects take lanes 240-246.  Rows: the 40 pairs
first (80 rows), then the 200 ladder constants, then the selects: 287.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench.apps import sc

N_HIST = 8
N_FACTORS = 5
ORDER = 5
KDE_C = 4.0
N_PAIRS = N_HIST * N_FACTORS
N_ROWS = 2 * N_PAIRS + N_PAIRS * ORDER + (N_HIST - 1)        # 287


def frame_inputs(rng: np.random.Generator, height: int, width: int) -> dict:
    """One frame's app inputs: ``x_t`` uniform in 0.1-0.9 per pixel and
    ``hist`` its ``N_HIST`` earlier values with Gaussian noise (0.15),
    clipped to [0, 1]; float32."""
    n = height * width
    x_t = rng.uniform(0.1, 0.9, size=n).astype(np.float32)
    hist = np.clip(x_t[:, None] + rng.normal(0.0, 0.15, (n, N_HIST)),
                   0.0, 1.0).astype(np.float32)
    return {"x_t": x_t, "hist": hist}


def exact(inputs: dict) -> np.ndarray:
    """Eq. 10 in float64: mean over history of ``exp(-4 |x_t - h_i|)``."""
    x_t = np.asarray(inputs["x_t"], np.float64)
    hist = np.asarray(inputs["hist"], np.float64)
    return np.exp(-KDE_C * np.abs(x_t[..., None] - hist)).mean(-1)


def _rows(x_t: jnp.ndarray, hist: jnp.ndarray) -> tuple:
    b = x_t.shape[0]
    pairs = []
    for g in range(N_PAIRS):
        pairs += [x_t, hist[:, g // N_FACTORS]]
    const = np.concatenate([
        np.tile(np.array([0.8 / k for k in range(1, ORDER + 1)],
                         np.float32), N_PAIRS),
        np.full(N_HIST - 1, 0.5, np.float32)])
    vals = jnp.concatenate([
        jnp.stack(pairs),
        jnp.broadcast_to(jnp.asarray(const)[:, None], (len(const), b))])
    lanes = np.concatenate([np.repeat(np.arange(N_PAIRS), 2),
                            N_PAIRS + np.arange(len(const))])
    return vals, jnp.asarray(lanes, jnp.uint32)


def reference(seed, inputs: dict, bitstream_length: int, elem0=0,
              bf16: bool = False) -> jnp.ndarray:
    """Decoded KDE output of a block of pixels, ``(B,)`` float32."""
    rows, lanes = _rows(jnp.asarray(inputs["x_t"], jnp.float32),
                        jnp.asarray(inputs["hist"], jnp.float32))
    s = sc.streams(seed, lanes, rows, bitstream_length, elem0, bf16)
    terms = []
    for i in range(N_HIST):
        factor = None
        for f in range(N_FACTORS):
            g = i * N_FACTORS + f
            d = s[2 * g] ^ s[2 * g + 1]                     # |x_t - h_i|
            c = [s[2 * N_PAIRS + g * ORDER + k] for k in range(ORDER)]
            e = ~(d & c[ORDER - 1])
            for k in range(ORDER - 2, -1, -1):               # Horner ladder
                e = ~((d & c[k]) & e)
            factor = e if factor is None else factor & e
        terms.append(factor)
    selects = iter(s[N_ROWS - (N_HIST - 1) + k] for k in range(N_HIST - 1))
    return sc.decode(sc.mean_tree(terms, selects), bitstream_length)
