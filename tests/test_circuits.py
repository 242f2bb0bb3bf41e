"""Functional correctness of the Fig. 5 stochastic netlists and the binary
baselines: every circuit, when *executed*, computes what the paper says.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import circuits, executor
from repro.core.gates import restrict_to_reliable

BL = 8192
TOL = 5.0 / np.sqrt(BL)


def run(net, values, bl=BL, seed=0):
    out = executor.execute_value(net, {k: jnp.float32(v) for k, v in values.items()},
                                 jax.random.key(seed), bl)
    return {k: float(v) for k, v in out.items()}


# ------------------------------ stochastic ops ------------------------------------

def test_all_stochastic_circuits_use_reliable_gates():
    for b in (circuits.sc_multiply, circuits.sc_scaled_add, circuits.sc_abs_sub,
              circuits.sc_scaled_div, circuits.sc_sqrt, circuits.sc_exp):
        restrict_to_reliable(b())    # must not raise


@given(st.floats(0.05, 0.95), st.floats(0.05, 0.95))
def test_multiply(a, b):
    out = run(circuits.sc_multiply(), {"a": a, "b": b})
    assert abs(out["out"] - a * b) < TOL


@given(st.floats(0.05, 0.95), st.floats(0.05, 0.95))
def test_scaled_add(a, b):
    out = run(circuits.sc_scaled_add(), {"a": a, "b": b})
    assert abs(out["out"] - (a + b) / 2) < TOL


@given(st.floats(0.0, 1.0), st.floats(0.0, 1.0))
def test_abs_sub_correlated(a, b):
    out = run(circuits.sc_abs_sub(), {"a": a, "b": b})
    assert abs(out["out"] - abs(a - b)) < TOL


@pytest.mark.parametrize("a,b", [(0.2, 0.6), (0.5, 0.5), (0.7, 0.1), (0.05, 0.9)])
def test_scaled_division_converges_to_a_over_a_plus_b(a, b):
    # The Gaines JK divider is a stochastic fixed-point iteration; tolerance
    # is looser (autocorrelated output stream).
    out = run(circuits.sc_scaled_div(), {"a": a, "b": b}, bl=16384)
    assert abs(out["Q_next"] - a / (a + b)) < 0.03


def test_sqrt_circuit_matches_its_documented_polynomial():
    # The reconstructed Fig. 5(e) circuit computes 1-(1-c*x)^2 (cost path).
    c = circuits.SQRT_C
    for x in (0.1, 0.4, 0.8):
        out = run(circuits.sc_sqrt(), {"a": x})
        expect = 1.0 - (1.0 - c * x) ** 2
        assert abs(out["out"] - expect) < TOL


@pytest.mark.parametrize("c", [0.5, 0.8, 1.0])
def test_exp_circuit_tracks_exponential(c):
    net = circuits.sc_exp(c)
    for x in (0.1, 0.5, 0.9):
        out = run(net, {"a": x})
        # 5th-order Maclaurin truncation error < 1e-3 for c*x <= 1.
        assert abs(list(out.values())[0] - np.exp(-c * x)) < TOL + 2e-3


def test_exp_rejects_c_out_of_unipolar_range():
    with pytest.raises(ValueError):
        circuits.sc_exp(1.5)


def test_mux_tree_computes_mean():
    from repro.core.gates import Netlist
    net = Netlist("tree")
    leaves = [net.add_pi(f"L{i}", value_key=f"v{i}") for i in range(4)]
    root = circuits.sc_mux_tree(leaves, net)
    net.set_outputs([root])
    vals = {f"v{i}": v for i, v in enumerate((0.1, 0.3, 0.5, 0.9))}
    out = run(net, vals)
    assert abs(out[root] - 0.45) < TOL


# ------------------------------- binary ops ---------------------------------------

@pytest.mark.parametrize("n_bits", [2, 3, 4, 8])
def test_binary_rca_exhaustive_small(n_bits):
    rng = np.random.default_rng(n_bits)
    n = min(1 << (2 * n_bits), 256)
    a = jnp.asarray(rng.integers(0, 1 << n_bits, n), jnp.uint32)
    b = jnp.asarray(rng.integers(0, 1 << n_bits, n), jnp.uint32)
    net = circuits.binary_ripple_carry_adder(n_bits)
    outs = executor.execute_binary(net, circuits.rca_prepare_inputs(a, b, n_bits))
    dec = circuits.rca_decode_outputs(outs, n_bits)
    assert (np.asarray(dec) == np.asarray(a) + np.asarray(b)).all()


def test_binary_nand_serial_adder_is_slower_than_row_parallel():
    from repro.core.scheduler import schedule
    serial = schedule(circuits.binary_adder_nand_serial(8))
    rowpar = schedule(circuits.binary_ripple_carry_adder(8))
    assert serial.logic_cycles > rowpar.logic_cycles
    assert serial.n_rows == 1


def test_binary_structural_circuits_have_plausible_size():
    # Cost-accounting constructions: sanity-check their scale against Table 2
    # (binary multiplier 16x161 cells => hundreds of gates; divider larger).
    mul = circuits.binary_multiplier(8)
    div = circuits.binary_divider(8)
    sqrt = circuits.binary_sqrt(8)
    exp = circuits.binary_exp(8)
    add = circuits.binary_ripple_carry_adder(8)
    assert len(mul.gates) > 3 * len(add.gates)
    assert len(div.gates) > len(mul.gates)
    assert len(sqrt.gates) > len(add.gates)
    assert len(exp.gates) > len(add.gates)
