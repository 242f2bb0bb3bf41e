"""HDP query batches through the request API: the array form of the inputs,
the compiled sequential path against the interpreter, and the device
trace's names for the program's layers.

A batch of HDP queries is a float32 array (N, 8) in ``HDP_KEYS`` order;
``appnet_inputs`` hands the executor one column per key, as it does for
LIT's windows.  The compiled path runs the JK divider as a scan over words
(``kernels/netlist_exec.run_sequential``), which must stay bit-identical to
the gate-by-gate interpreter.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apps, dispatch, executor
from repro.core.plan import compile_plan
from repro.serve.apps import app_netlist


def _queries(seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.uniform(0.1, 0.9, size=(n, len(apps.HDP_KEYS))).astype(
        np.float32)


def _run(values, key, bl, backend=None) -> np.ndarray:
    (out,) = executor.run(executor.ExecRequest(
        app_netlist("hdp"), values, key,
        executor.ExecOptions(bitstream_length=bl, decode=True,
                             backend=backend))).values()
    return np.asarray(out)


def test_array_and_dict_inputs_agree_bit_for_bit():
    v = _queries(3, 96)
    as_dict = {k: v[:, i].copy() for i, k in enumerate(apps.HDP_KEYS)}
    key = jax.random.key(11)
    got = _run(apps.appnet_inputs("hdp", v=v), key, 128)
    want = _run(apps.appnet_inputs("hdp", v=as_dict), key, 128)
    assert got.shape == (96,)
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="HDP_KEYS order"):
        apps.appnet_inputs("hdp", v=v[:, :7])


@pytest.mark.parametrize("bl", [64, 256])
def test_compiled_equals_interpreter_at_odd_batch(bl):
    values = apps.appnet_inputs("hdp", v=_queries(2**31 + bl, 333))
    key = jax.random.key(bl + 5)
    got = _run(values, key, bl)
    want = _run(values, key, bl, backend="reference")
    assert got.shape == (333,)
    np.testing.assert_array_equal(got, want)
    assert len(np.unique(got)) > 10                  # not a constant stream


def test_lowered_program_names_its_layers():
    """The HDP program's SNG, scan and decode keep their name scopes in the
    compiled HLO's ``op_name`` metadata, which the profiler reports as
    ``tf_op``; the plan is sequential, so no ``sc.passes`` scope stands
    outside the scan."""
    plan = compile_plan(app_netlist("hdp"))
    assert plan.is_sequential
    values = {k: jnp.asarray(v) for k, v in
              apps.appnet_inputs("hdp", v=_queries(4, 64)).items()}
    text = dispatch._execute_compiled.lower(
        plan, values, jax.random.key(0), None, 256, 0.0, False,
        decode=True).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    first = {m.group(0) for p in paths
             if (m := re.search(r"\bsc\.[a-z]+", p))}
    assert {"sc.sng", "sc.scan", "sc.decode"} <= first
    assert "sc.passes" not in first
