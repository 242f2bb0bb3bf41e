"""Per-kernel tests: Pallas (interpret mode) vs the pure-jnp ref.py oracle.

Every kernel uses the same counter-based RNG as its oracle, so equality is
*exact* (bit-for-bit), not approximate; statistical tests then check the SC
semantics against float math.  Hypothesis sweeps shapes/odd sizes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.common import gen_packed_bits, hash_u32, threshold_u32
from repro.kernels.packed_logic import packed_logic
from repro.kernels.popcount_tree import popcount_hier
from repro.kernels.sc_matmul import sc_matmul
from repro.kernels.sng import lane_seeds, sng_pack, sng_words

KEY = jax.random.key(0)


# ------------------------------- common.py ---------------------------------------

def test_hash_u32_is_deterministic_and_mixing():
    x = jnp.arange(1 << 16, dtype=jnp.uint32)
    h = hash_u32(x)
    # no collisions over consecutive counters (murmur3 finalizer is a bijection)
    assert len(np.unique(np.asarray(h))) == 1 << 16
    # bit balance: each output bit ~half set
    bits = np.unpackbits(np.asarray(h).view(np.uint8)).mean()
    assert abs(bits - 0.5) < 0.01


def test_threshold_endpoints():
    assert int(threshold_u32(jnp.float32(0.0))) == 0
    assert int(threshold_u32(jnp.float32(1.0))) == 0xFFFFFFFF


def test_gen_packed_bits_statistics():
    base = (jnp.arange(2048, dtype=jnp.uint32) * 32)
    words = gen_packed_bits(jnp.uint32(9), base, jnp.full((2048,), 0.3, jnp.float32))
    rate = float(jax.lax.population_count(words).sum()) / (2048 * 32)
    assert abs(rate - 0.3) < 0.01


# ------------------------------- sng kernel --------------------------------------

@settings(max_examples=10)
@given(st.integers(1, 300), st.sampled_from([32, 64, 128, 256]))
def test_sng_kernel_equals_ref_all_shapes(n, bl):
    p = jax.random.uniform(jax.random.key(n), (n,))
    k = sng_pack(p, bl, interpret=True)
    r = ref.sng_pack_ref(p, bl)
    assert (k == r).all()


def test_sng_values_match_probabilities():
    p = jnp.asarray([0.0, 0.2, 0.5, 0.8, 1.0], jnp.float32)
    words = sng_pack(p, 4096, interpret=True)
    got = jax.lax.population_count(words).sum(-1) / 4096.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(p), atol=0.05)


def test_sng_is_tiling_independent():
    p = jax.random.uniform(KEY, (100,))
    a = sng_pack(p, 128, block=256, interpret=True)
    b = sng_pack(p, 128, block=32, interpret=True)
    assert (a == b).all()


# --------------------------- batched stream-table sng -----------------------------

@settings(max_examples=10)
@given(st.integers(1, 24), st.integers(1, 40), st.sampled_from([32, 64, 128]))
def test_sng_words_pallas_equals_ref_all_shapes(n, b, bl):
    thr = threshold_u32(jax.random.uniform(jax.random.key(n * 100 + b), (n, b)))
    seeds = lane_seeds(jnp.uint32(5), jnp.arange(n, dtype=jnp.uint32))
    k = sng_words(seeds, thr, bl // 32, use_pallas=True, interpret=True)
    r = ref.sng_words_ref(seeds, thr, bl // 32)
    assert k.shape == (n, b, bl // 32)
    assert (k == r).all()


def test_sng_words_block_independent_and_equals_ref():
    # 13 rows: a full and a ragged 8-row block; 17-element blocks: ragged too.
    thr = threshold_u32(jax.random.uniform(KEY, (13, 100)))
    seeds = lane_seeds(jnp.uint32(1), jnp.arange(13, dtype=jnp.uint32))
    a = sng_words(seeds, thr, 4, use_pallas=True, block_elems=256, interpret=True)
    b = sng_words(seeds, thr, 4, use_pallas=True, block_elems=17, interpret=True)
    assert (a == b).all()
    assert (a == ref.sng_words_ref(seeds, thr, 4)).all()


def test_sng_words_rows_independent_of_stacking():
    # A row's stream depends only on (seed, element, bit) — stacking more
    # rows alongside it must not change its bits (the property bank-level
    # generation relies on to stay bit-identical to per-member generation).
    thr = threshold_u32(jax.random.uniform(jax.random.key(3), (4, 16)))
    seeds = lane_seeds(jnp.uint32(2), jnp.arange(4, dtype=jnp.uint32))
    full = sng_words(seeds, thr, 8)
    solo = sng_words(seeds[2:3], thr[2:3], 8)
    assert (full[2] == solo[0]).all()


def test_sng_words_shared_lane_shares_uniforms():
    # Equal row seeds (one correlation group) => streams are threshold-nested:
    # wherever the lower-threshold row has a 1, the higher-threshold row must.
    thr = jnp.stack([threshold_u32(jnp.full((64,), 0.3, jnp.float32)),
                     threshold_u32(jnp.full((64,), 0.7, jnp.float32))])
    seeds = lane_seeds(jnp.uint32(4), jnp.zeros((2,), jnp.uint32))
    w = sng_words(seeds, thr, 8)
    assert (w[0] & ~w[1]).sum() == 0


# ----------------------------- packed logic --------------------------------------

@pytest.mark.parametrize("op,n_in", [("not", 1), ("and", 2), ("nand", 2),
                                     ("or", 2), ("nor", 2), ("xor", 2), ("mux", 3)])
def test_packed_logic_matches_ref(op, n_in):
    args = [jax.random.bits(jax.random.key(i), (16, 256), dtype=jnp.uint32)
            for i in range(n_in)]
    k = packed_logic(op, *args, interpret=True)
    r = ref.sc_eltwise_ref(op, *args)
    assert (k == r).all()


@settings(max_examples=10)
@given(st.integers(1, 40), st.integers(1, 300))
def test_packed_logic_odd_shapes(rows, words):
    a = jax.random.bits(jax.random.key(rows), (rows, words), dtype=jnp.uint32)
    b = jax.random.bits(jax.random.key(words), (rows, words), dtype=jnp.uint32)
    assert (packed_logic("nand", a, b, interpret=True)
            == ref.sc_eltwise_ref("nand", a, b)).all()


# ---------------------------- popcount tree --------------------------------------

@settings(max_examples=10)
@given(st.integers(1, 64), st.integers(1, 300))
def test_popcount_kernel_matches_ref(n, w):
    words = jax.random.bits(jax.random.key(n * 1000 + w), (n, w), dtype=jnp.uint32)
    k = popcount_hier(words, interpret=True)
    r = ref.popcount_hier_ref(words, group=16)
    exact = np.array([[bin(int(x)).count("1") for x in row]
                      for row in np.asarray(words)]).sum(-1)
    assert (np.asarray(k) == exact).all()
    assert (np.asarray(r) == exact).all()


# ------------------------------ sc matmul ----------------------------------------

@settings(max_examples=8)
@given(st.integers(1, 24), st.integers(1, 48), st.integers(1, 48),
       st.sampled_from([32, 64, 128]))
def test_sc_matmul_kernel_equals_ref(m, k, n, bl):
    a = jax.random.uniform(jax.random.key(m), (m, k))
    w = jax.random.uniform(jax.random.key(n), (k, n))
    out_k = sc_matmul(a, w, bl, bm=8, bn=16, bk=16, interpret=True)
    out_r = ref.sc_matmul_ref(a, w, bl)
    assert (out_k == out_r).all()


def test_sc_matmul_tiling_independent():
    a = jax.random.uniform(jax.random.key(1), (16, 64))
    w = jax.random.uniform(jax.random.key(2), (64, 24))
    o1 = sc_matmul(a, w, 64, bm=4, bn=8, bk=16, interpret=True)
    o2 = sc_matmul(a, w, 64, bm=16, bn=24, bk=64, interpret=True)
    assert (o1 == o2).all()


def test_sc_matmul_unbiased_and_converges_with_bl():
    a = jax.random.uniform(jax.random.key(3), (8, 128))
    w = jax.random.uniform(jax.random.key(4), (128, 8))
    exact = a @ w
    errs = []
    for bl in (32, 128, 512):
        approx = ref.sc_matmul_ref(a, w, bl)
        errs.append(float(jnp.abs(approx - exact).mean()))
    assert errs[2] < errs[0]                 # error shrinks with BL
    assert errs[2] / float(jnp.abs(exact).mean()) < 0.05


def test_ops_dispatch_paths_agree():
    a = jax.random.uniform(jax.random.key(5), (8, 32))
    w = jax.random.uniform(jax.random.key(6), (32, 8))
    assert (ops.sc_matmul(a, w, 64, use_pallas=True)
            == ops.sc_matmul(a, w, 64, use_pallas=False)).all()
    p = jax.random.uniform(jax.random.key(7), (50,))
    assert (ops.sng(p, 64, use_pallas=True) == ops.sng(p, 64, use_pallas=False)).all()
    thr = threshold_u32(jax.random.uniform(jax.random.key(8), (4, 20)))
    seeds = lane_seeds(jnp.uint32(3), jnp.arange(4, dtype=jnp.uint32))
    assert (ops.sng_table(seeds, thr, 64, use_pallas=True)
            == ops.sng_table(seeds, thr, 64, use_pallas=False)).all()
    words = jax.random.bits(KEY, (16, 8), dtype=jnp.uint32)
    assert (ops.stob_counts(words, use_pallas=True)
            == ops.stob_counts(words, use_pallas=False)).all()
