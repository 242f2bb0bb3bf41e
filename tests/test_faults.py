"""STT-MRAM fault models (core/faults.py) through every execution path.

Pins for the PR-8 fault taxonomy:

  * ``FaultModel(flip_rate=r)`` is bit-identical to the legacy
    ``bitflip_rate=r`` path (the raw-fkey transient discipline);
  * compiled == reference under a composite model, both key_modes;
  * faulty runs are deterministic in ``flip_key`` (same key -> same bits,
    different key -> different bits) and a null model IS the clean path;
  * rate extremes pin the mask semantics: all-stuck-0 reads zero,
    all-stuck-1 reads one, sa1 wins over sa0;
  * static components (``dead_cols`` spans, ``sa0/sa1_words``) need no key
    and mask exactly the declared cells;
  * wear accounting (``worn``) is monotone and saturates at rate 1;
  * bank/template execution and serving reproduce standalone bits;
  * validation: mutual exclusion with ``bitflip_rate``, required
    ``flip_key``, malformed models raise.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitstream as bs
from repro.core import circuits, executor
from repro.core.executor import ExecOptions, ExecRequest
from repro.core.faults import (FaultModel, apply_faults, injecting,
                               normalize_fault_model)
from repro.serve import BankServer, circuit_request

KEY = jax.random.key(7)
FLIP = jax.random.key(77)
BL = 256
W = BL // 32

MUL = circuits.sc_multiply()
SADD = circuits.sc_scaled_add()
DIV = circuits.sc_scaled_div()
VALUES = {"a": 0.3, "b": 0.7}

COMPOSITE = FaultModel(flip_rate=0.02, stuck0_rate=0.03, stuck1_rate=0.01,
                       dead_row_rate=0.05)


def tree_eq(a, b) -> bool:
    if sorted(a) != sorted(b):
        return False
    return all(bool(jnp.array_equal(a[k], b[k])) for k in a)


# ------------------------- model construction / views -------------------------


def test_null_model_normalizes_to_none():
    assert normalize_fault_model(None) is None
    assert normalize_fault_model(FaultModel()) is None
    assert normalize_fault_model(FaultModel(flip_rate=0.0)) is None
    m = FaultModel(stuck0_rate=0.1)
    assert normalize_fault_model(m) is m


def test_model_is_hashable_and_frozen():
    m = FaultModel(flip_rate=0.1, dead_cols=((0, 4),))
    assert hash(m) == hash(FaultModel(flip_rate=0.1, dead_cols=((0, 4),)))
    with pytest.raises(dataclasses.FrozenInstanceError):
        m.flip_rate = 0.2


def test_needs_keys_vs_static_only():
    assert FaultModel(flip_rate=0.1).needs_keys
    assert FaultModel(stuck0_rate=0.1).needs_keys
    assert FaultModel(dead_row_rate=0.1).needs_keys
    static = FaultModel(dead_cols=((0, 8),), sa1_words=(1,) * W)
    assert not static.needs_keys
    assert not static.is_null


def test_wear_is_monotone_and_saturates():
    m = FaultModel(stuck0_rate=0.1, wear_stuck_per_pass=0.05)
    assert m.effective_stuck0 == pytest.approx(0.1)
    worn = m.worn(3)
    assert worn.wear_passes == 3
    assert worn.effective_stuck0 == pytest.approx(0.25)
    assert worn.worn(2).wear_passes == 5
    assert m.worn(100).effective_stuck0 == 1.0   # saturates at a full array
    assert m.wear_passes == 0                    # worn() never mutates


def test_model_validation_errors():
    with pytest.raises(ValueError, match="flip_rate"):
        FaultModel(flip_rate=1.5)
    with pytest.raises(ValueError, match="dead_cols"):
        FaultModel(dead_cols=((4, 2),))
    with pytest.raises(ValueError, match="sa0_words"):
        FaultModel(sa0_words=(1 << 40,))
    with pytest.raises(ValueError, match="wear_passes"):
        FaultModel(wear_passes=-1)
    with pytest.raises(TypeError, match="FaultModel"):
        normalize_fault_model(0.1)


# ------------------------------ mask semantics --------------------------------


def test_apply_faults_null_model_is_flip_bits():
    words = bs.generate(KEY, jnp.float32(0.5), BL)
    from repro.core import sc_ops
    got = apply_faults(FLIP, words, 0.1, None)
    assert jnp.array_equal(got, sc_ops.flip_bits(FLIP, words, 0.1))


def test_stuck0_rate_one_reads_zero():
    words = bs.generate(KEY, jnp.float32(0.9), BL)
    got = apply_faults(FLIP, words, 0.0, FaultModel(stuck0_rate=1.0))
    assert int(jnp.sum(got)) == 0


def test_stuck1_rate_one_reads_one():
    words = bs.generate(KEY, jnp.float32(0.1), BL)
    got = apply_faults(FLIP, words, 0.0, FaultModel(stuck1_rate=1.0))
    assert bool(jnp.all(got == jnp.uint32(0xFFFFFFFF)))


def test_sa1_wins_over_sa0():
    words = bs.generate(KEY, jnp.float32(0.5), BL)
    full = (0xFFFFFFFF,) * W
    m = FaultModel(sa0_words=full, sa1_words=full)
    got = apply_faults(FLIP, words, 0.0, m)
    assert bool(jnp.all(got == jnp.uint32(0xFFFFFFFF)))


def test_dead_cols_mask_exact_bits():
    words = jnp.full((W,), jnp.uint32(0xFFFFFFFF))
    got = np.asarray(apply_faults(FLIP, words, 0.0,
                                  FaultModel(dead_cols=((0, 3), (40, 42)))))
    bits = np.asarray(bs.unpack_bits(jnp.asarray(got))).reshape(-1)
    dead = {0, 1, 2, 40, 41}
    assert all(int(bits[b]) == (0 if b in dead else 1) for b in range(BL))


def test_sa_words_length_mismatch_raises():
    words = jnp.zeros((W,), jnp.uint32)
    with pytest.raises(ValueError, match="sa0_words"):
        apply_faults(FLIP, words, 0.0, FaultModel(sa0_words=(1, 2)))
    with pytest.raises(ValueError, match="sa1_words"):
        apply_faults(FLIP, words, 0.0, FaultModel(sa1_words=(1, 2)))


def test_dead_row_rate_one_kills_every_stream():
    words = bs.generate(KEY, jnp.full((5,), 0.8), BL)
    got = apply_faults(FLIP, words, 0.0, FaultModel(dead_row_rate=1.0))
    assert int(jnp.sum(got)) == 0


# ------------------------- executor-level bit identity ------------------------


@pytest.mark.parametrize("key_mode", ["batched", "legacy"])
@pytest.mark.parametrize("rate", [0.05, 0.2])
def test_flip_rate_model_matches_legacy_bitflip(key_mode, rate):
    legacy = executor.execute(MUL, VALUES, KEY, BL, bitflip_rate=rate,
                              flip_key=FLIP, key_mode=key_mode)
    model = executor.execute(MUL, VALUES, KEY, BL, flip_key=FLIP,
                             key_mode=key_mode,
                             fault_model=FaultModel(flip_rate=rate))
    assert tree_eq(legacy, model)


@pytest.mark.parametrize("key_mode", ["batched", "legacy"])
@pytest.mark.parametrize("net", [MUL, SADD, DIV],
                         ids=lambda n: n.name)
def test_compiled_matches_reference_under_faults(key_mode, net):
    kw = dict(flip_key=FLIP, key_mode=key_mode, fault_model=COMPOSITE)
    compiled = executor.execute(net, VALUES, KEY, BL, backend="compiled", **kw)
    reference = executor.execute(net, VALUES, KEY, BL, backend="reference",
                                 **kw)
    assert tree_eq(compiled, reference)


def test_faulty_run_deterministic_in_flip_key():
    a = executor.execute(MUL, VALUES, KEY, BL, flip_key=FLIP,
                         fault_model=COMPOSITE)
    b = executor.execute(MUL, VALUES, KEY, BL, flip_key=FLIP,
                         fault_model=COMPOSITE)
    c = executor.execute(MUL, VALUES, KEY, BL,
                         flip_key=jax.random.key(123456),
                         fault_model=COMPOSITE)
    assert tree_eq(a, b)
    assert not tree_eq(a, c)


def test_null_model_is_clean_path():
    clean = executor.execute(MUL, VALUES, KEY, BL)
    null = executor.execute(MUL, VALUES, KEY, BL,
                            fault_model=FaultModel())
    assert tree_eq(clean, null)


def test_static_model_needs_no_flip_key():
    m = FaultModel(dead_cols=((0, 32),))
    out = executor.execute(MUL, VALUES, KEY, BL, fault_model=m)
    clean = executor.execute(MUL, VALUES, KEY, BL)
    assert sorted(out) == sorted(clean)
    # The first dead word zeroes 32 of 256 positions on every stream.
    assert int(np.asarray(out["out"])[..., 0]) == 0


def test_stuck_faults_degrade_value():
    v_clean = executor.execute_value(DIV, VALUES, KEY, BL)["Q_next"]
    v_fault = executor.execute_value(
        DIV, VALUES, KEY, BL, flip_key=FLIP,
        fault_model=FaultModel(stuck0_rate=0.3))["Q_next"]
    assert float(v_fault) < float(v_clean)


def test_mutual_exclusion_and_missing_key_raise():
    with pytest.raises(ValueError, match="not both"):
        executor.execute(MUL, VALUES, KEY, BL, bitflip_rate=0.1,
                         flip_key=FLIP, fault_model=COMPOSITE)
    with pytest.raises(ValueError, match="requires"):
        executor.execute(MUL, VALUES, KEY, BL, fault_model=COMPOSITE)


# ------------------------- bank / serving bit identity ------------------------


def test_bank_run_matches_standalone_under_faults():
    reqs = [ExecRequest(MUL, {"a": 0.2 + 0.1 * i, "b": 0.6},
                        jax.random.key(i),
                        ExecOptions(bitstream_length=BL, flip_key=FLIP,
                                    fault_model=COMPOSITE))
            for i in range(3)]
    merged = executor.run(reqs)
    for req, got in zip(reqs, merged):
        assert tree_eq(got, executor.run(req))


def test_served_faulty_requests_match_standalone():
    model = FaultModel(flip_rate=0.05, stuck0_rate=0.05)
    with BankServer(max_slots=4) as srv:
        reqs = [circuit_request(MUL, {"a": 0.1 * (i + 1), "b": 0.5},
                                jax.random.key(i), BL,
                                flip_key=jax.random.key(1000 + i),
                                fault_model=model)
                for i in range(4)]
        outs = [t.result() for t in [srv.submit(r) for r in reqs]]
    for req, got in zip(reqs, outs):
        ref = executor.run(req, options=dataclasses.replace(
            req.options, decode=True))
        assert tree_eq(got, ref)


@given(st.floats(min_value=0.0, max_value=1.0),
       st.floats(min_value=0.0, max_value=1.0))
@settings(max_examples=15)
def test_property_flip_rate_model_equals_legacy(rate, frac):
    """Any flip rate: the model path reproduces the legacy path bit-exactly."""
    values = {"a": float(frac), "b": 0.5}
    legacy = executor.execute(MUL, values, KEY, BL, bitflip_rate=float(rate),
                              flip_key=FLIP)
    model = executor.execute(MUL, values, KEY, BL, flip_key=FLIP,
                             fault_model=FaultModel(flip_rate=float(rate)))
    assert tree_eq(legacy, model)


def test_injecting_predicate():
    assert not injecting(0.0, None)
    assert injecting(0.1, None)
    assert injecting(0.0, FaultModel(stuck0_rate=0.1))
    # normalize first: dispatch never sees a null model as "injecting".
    assert normalize_fault_model(FaultModel()) is None
