"""Packed-bitstream layer tests: generation statistics, packing, gate algebra."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import bitstream as bs

KEY = jax.random.key(42)
BL = 4096
TOL = 4.0 / np.sqrt(BL)  # ~4 sigma of Bernoulli noise


def val(words):
    return float(bs.to_value(words, BL))


def test_pack_unpack_roundtrip():
    w = jax.random.bits(KEY, (5, 7), dtype=jnp.uint32)
    assert (bs.pack_bits(bs.unpack_bits(w)) == w).all()


def test_generate_value_matches_probability():
    p = jnp.asarray([0.0, 0.1, 0.25, 0.5, 0.9, 1.0], jnp.float32)
    streams = bs.generate(KEY, p, BL)
    got = bs.to_value(streams, BL)
    np.testing.assert_allclose(np.asarray(got), np.asarray(p), atol=TOL)
    # Degenerate endpoints must be (nearly) deterministic.
    assert float(got[0]) == 0.0
    assert float(got[-1]) >= 1.0 - 2.0 / BL


def test_popcount_matches_numpy():
    w = jax.random.bits(KEY, (3, 8), dtype=jnp.uint32)
    ref = np.array([[bin(int(x)).count("1") for x in row] for row in np.asarray(w)])
    assert (np.asarray(jax.lax.population_count(w)) == ref).all()
    assert (np.asarray(bs.popcount(w)) == ref.sum(-1)).all()


@given(st.floats(0.05, 0.95), st.floats(0.05, 0.95))
def test_and_multiplies(pa, pb):
    a = bs.generate(jax.random.key(1), jnp.float32(pa), BL)
    b = bs.generate(jax.random.key(2), jnp.float32(pb), BL)
    assert abs(val(a & b) - pa * pb) < TOL


@given(st.floats(0.05, 0.95), st.floats(0.05, 0.95))
def test_mux_scaled_adds(pa, pb):
    a = bs.generate(jax.random.key(3), jnp.float32(pa), BL)
    b = bs.generate(jax.random.key(4), jnp.float32(pb), BL)
    s = bs.generate(jax.random.key(5), jnp.float32(0.5), BL)
    assert abs(val(bs.mux(a, b, s)) - (pa + pb) / 2) < TOL


@given(st.floats(0.0, 1.0), st.floats(0.0, 1.0))
def test_correlated_xor_is_abs_difference(pa, pb):
    a, b = bs.generate_correlated(jax.random.key(6), [jnp.float32(pa), jnp.float32(pb)], BL)
    assert abs(val(a ^ b) - abs(pa - pb)) < TOL


def test_independent_xor_is_not_abs_difference():
    # Sanity: independence breaks the |a-b| identity (value = a(1-b)+b(1-a)).
    a = bs.generate(jax.random.key(7), jnp.float32(0.5), BL)
    b = bs.generate(jax.random.key(8), jnp.float32(0.5), BL)
    assert abs(val(a ^ b) - 0.5) < TOL        # not 0.0


def test_not_complements():
    a = bs.generate(KEY, jnp.float32(0.3), BL)
    assert abs(val(~a) - 0.7) < TOL


def test_maj3_identity():
    ws = [jax.random.bits(jax.random.key(i), (4,), dtype=jnp.uint32) for i in range(3)]
    got = bs.maj3(*ws)
    ref = (ws[0] & ws[1]) | (ws[0] & ws[2]) | (ws[1] & ws[2])
    assert (got == ref).all()


def test_maj5_matches_bit_count():
    ws = [jax.random.bits(jax.random.key(10 + i), (2,), dtype=jnp.uint32) for i in range(5)]
    got = bs.unpack_bits(bs.maj5(*ws))
    bits = sum(bs.unpack_bits(w).astype(np.int32) for w in ws)
    assert (np.asarray(got) == (np.asarray(bits) >= 3)).all()


def test_bad_bitstream_length_rejected():
    with pytest.raises(ValueError):
        bs.n_words(100)


def test_threshold_top_of_range_is_exact():
    # Regression: float32 rounds 2^32 - 1 up to 2^32, so the old float-side
    # minimum was a no-op and p=1.0 hit an out-of-range float->uint32 cast
    # that only "worked" because XLA:CPU saturates (undefined elsewhere).
    # The integer-side clamp must pin the top of the range on every backend.
    assert int(bs._threshold_u32(jnp.float32(1.0))) == 0xFFFFFFFF
    assert int(bs._threshold_u32(jnp.float32(1.0 - 2.0 ** -32))) == 0xFFFFFFFF
    assert int(bs._threshold_u32(jnp.float32(0.0))) == 0
    # Monotone and in-range across the interior.
    ps = jnp.linspace(0.0, 1.0, 257, dtype=jnp.float32)
    th = np.asarray(bs._threshold_u32(ps), dtype=np.uint64)
    assert (np.diff(th.astype(np.int64)) >= 0).all()
    assert th[-1] == 0xFFFFFFFF


def test_p_one_decodes_to_one():
    for bl in (32, 1024):
        v = bs.to_value(bs.generate(KEY, jnp.float32(1.0), bl), bl)
        assert float(v) >= 1.0 - 2.0 / bl
    near = jnp.float32(1.0 - 2.0 ** -32)   # rounds to 1.0 in float32
    v = bs.to_value(bs.generate(KEY, near, 1024), 1024)
    assert float(v) >= 1.0 - 2.0 / 1024
