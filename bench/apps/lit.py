"""LIT: Sauvola local image thresholding over a 9x9 window (paper sec. 5.3,
Eq. 5-6), its inputs, its plain stochastic reference and its exact value.

The reference evaluates the paper's LIT circuit (Fig. 9(a)) on packed
streams with the key discipline of ``sc.py``.  Its 406 stream rows, in lane
order: two independent copies ``a1_i, a2_i`` of each window pixel ``i``
(lanes ``2i, 2i+1``); the 80 value-0.5 selects of the mean tree over the
squares ``a1_i & a2_i``, then of the tree over ``a1``, then over ``a2``;
then the constants 0.9, 0.9, 1.0 and 0.5.  Out: ``T = E[a] * (sigma+1)/2``
with ``sigma = sqrt(|E[a^2] - E[a]^2|)`` as two ANDs with 0.9 ORed.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench.apps import sc

WINDOW = 9
TAPS = WINDOW * WINDOW
N_ROWS = 2 * TAPS + 3 * (TAPS - 1) + 4          # 406


def frame_inputs(rng: np.random.Generator, height: int, width: int) -> dict:
    """One frame's app inputs: every pixel's edge-padded 9x9 window,
    ``{"a": (height * width, 81)}`` float32, pixels uniform in 0.05-0.95."""
    img = rng.uniform(0.05, 0.95, size=(height, width)).astype(np.float32)
    pad = np.pad(img, WINDOW // 2, mode="edge")
    win = np.lib.stride_tricks.sliding_window_view(pad, (WINDOW, WINDOW))
    return {"a": np.ascontiguousarray(win.reshape(height * width, TAPS))}


def exact(inputs: dict) -> np.ndarray:
    """Eq. 5-6 in float64: ``m * (sqrt(|E[a^2] - m^2|) + 1) / 2``."""
    a = np.asarray(inputs["a"], np.float64)
    m = a.mean(-1)
    sigma = np.sqrt(np.abs((a * a).mean(-1) - m * m))
    return m * (sigma + 1.0) / 2.0


def _rows(a: jnp.ndarray) -> jnp.ndarray:
    """``(406, B)`` row values for a block ``a`` of ``(B, 81)`` windows."""
    b = a.shape[0]
    pix = jnp.repeat(a.T, 2, axis=0)                        # a0, a0, a1, ...
    const = np.concatenate([np.full(3 * (TAPS - 1), 0.5),
                            [0.9, 0.9, 1.0, 0.5]]).astype(np.float32)
    return jnp.concatenate(
        [pix, jnp.broadcast_to(jnp.asarray(const)[:, None], (len(const), b))])


def reference(seed, inputs: dict, bitstream_length: int, elem0=0,
              bf16: bool = False) -> jnp.ndarray:
    """Decoded LIT output of a block of pixels, ``(B,)`` float32."""
    rows = _rows(jnp.asarray(inputs["a"], jnp.float32))
    s = sc.streams(seed, jnp.arange(N_ROWS, dtype=jnp.uint32), rows,
                   bitstream_length, elem0, bf16)
    a1 = [s[2 * i] for i in range(TAPS)]
    a2 = [s[2 * i + 1] for i in range(TAPS)]
    selects = iter(s[2 * TAPS + k] for k in range(3 * (TAPS - 1)))
    m_sq = sc.mean_tree([x & y for x, y in zip(a1, a2)], selects)
    m_a1 = sc.mean_tree(a1, selects)
    m_a2 = sc.mean_tree(a2, selects)
    var = m_sq ^ (m_a1 & m_a2)
    c1, c2, ones, half = (s[N_ROWS - 4 + k] for k in range(4))
    sigma = (var & c1) | (var & c2)
    scaled = sc.mux(sigma, ones, half)                      # (sigma + 1) / 2
    return sc.decode(m_a1 & scaled, bitstream_length)
