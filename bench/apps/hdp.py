"""HDP: Bayesian heart-disaster prediction of one patient record (paper sec.
5.3, Eq. 8-9), its inputs, its plain stochastic reference and its exact
value.

A query is 8 probabilities, in ``KEYS`` order.  The reference evaluates the
paper's HDP circuit (Fig. 9(c)) on packed streams with the key discipline
of ``sc.py``.  Its 11 stream rows, on lanes 0..10 and with no correlation
groups: ``p_ed, p_end, p_d, p_ned, p_nend, p_d, p_e, p_bp, p_cp, p_bp,
p_cp``.  Eq. 9 is three MUXes, ``inner_e = mux(p_ed, p_end, sel=p_d)``,
``inner_ne = mux(p_ned, p_nend, sel=p_d')`` and ``p_hd = mux(inner_e,
inner_ne, sel=p_e)``; Eq. 8's numerator is ``p_bp & p_cp & p_hd`` and its
complement term ``~p_bp' & ~p_cp' & ~p_hd``; the JK-flip-flop divider
emits ``out_t = Q_t ? ~den_t : num_t`` with ``Q_{t+1} = out_t``, ``Q_0 = 0``,
over time steps ``t = 32 w + b`` (bit ``b`` of word ``w``).

Where the circuit departs from Eq. 8-9 (so its decoded value differs from
``exact`` by more than stream noise):

* the two inner MUXes select on independent copies of ``p_d`` (lanes 2 and
  5), and the complement term takes independent copies of ``p_bp`` and
  ``p_cp`` (lanes 9 and 10), negated;
* ``~p_hd`` is the NOT of the same ``p_hd`` stream the numerator uses, so
  ``num`` and ``den`` are never both set;
* the divider's mean is ``P(num) / (P(num) + P(den))`` only in its steady
  state: it starts from ``Q_0 = 0`` with no warm-up bits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.apps import sc

#: A query's 8 probabilities, in the order of the last axis of ``v``.
KEYS = ("p_bp", "p_cp", "p_e", "p_d", "p_ed", "p_end", "p_ned", "p_nend")
#: The stream rows in lane order, each by its key.
ROWS = ("p_ed", "p_end", "p_d", "p_ned", "p_nend", "p_d", "p_e", "p_bp",
        "p_cp", "p_bp", "p_cp")
N_ROWS = len(ROWS)                                   # 11


def frame_inputs(rng: np.random.Generator, height: int, width: int) -> dict:
    """``height * width`` queries, ``{"v": (height * width, 8)}`` float32,
    each probability uniform in 0.1-0.9."""
    n = height * width
    return {"v": rng.uniform(0.1, 0.9, size=(n, len(KEYS))).astype(
        np.float32)}


def exact(inputs: dict) -> np.ndarray:
    """Eq. 8-9 in float64."""
    v = np.asarray(inputs["v"], np.float64)
    p = {k: v[..., i] for i, k in enumerate(KEYS)}
    p_hd = ((p["p_ed"] * p["p_d"] + p["p_end"] * (1 - p["p_d"])) * p["p_e"]
            + (p["p_ned"] * p["p_d"] + p["p_nend"] * (1 - p["p_d"]))
            * (1 - p["p_e"]))
    num = p["p_bp"] * p["p_cp"] * p_hd
    return num / (num + (1 - p["p_bp"]) * (1 - p["p_cp"]) * (1 - p_hd))


def divide(num: jnp.ndarray, den: jnp.ndarray) -> jnp.ndarray:
    """The JK divider over packed ``(B, W)`` streams, bit by bit: returns
    the packed output stream."""
    shifts = jnp.arange(sc.WORD_BITS, dtype=jnp.uint32)

    def time_major(words):                          # (B, W) -> (W * 32, B)
        bits = (words[..., None] >> shifts) & jnp.uint32(1)
        return bits.reshape(words.shape[0], -1).T

    def step(q, nd):
        n, d = nd
        out = jnp.where(q == 1, jnp.uint32(1) - d, n)
        return out, out

    q0 = jnp.zeros(num.shape[:1], jnp.uint32)
    _, out = jax.lax.scan(step, q0, (time_major(num), time_major(den)))
    bits = out.T.reshape(num.shape + (sc.WORD_BITS,))
    return jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint32)


def reference(seed, inputs: dict, bitstream_length: int, elem0=0,
              bf16: bool = False) -> jnp.ndarray:
    """Decoded HDP output of a block of queries, ``(B,)`` float32."""
    v = jnp.asarray(inputs["v"], jnp.float32)
    rows = jnp.stack([v[:, KEYS.index(k)] for k in ROWS])
    s = sc.streams(seed, jnp.arange(N_ROWS, dtype=jnp.uint32), rows,
                   bitstream_length, elem0, bf16)
    inner_e = sc.mux(s[0], s[1], s[2])
    inner_ne = sc.mux(s[3], s[4], s[5])
    p_hd = sc.mux(inner_e, inner_ne, s[6])
    num = s[7] & s[8] & p_hd
    den = ~s[9] & ~s[10] & ~p_hd
    return sc.decode(divide(num, den), bitstream_length)
