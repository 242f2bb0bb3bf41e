"""HDP query batches through the request API: the array form of the inputs,
the compiled sequential path against the interpreter, the split of the scan
at its state cone, and the device trace's names for the program's layers.

A batch of HDP queries is a float32 array (N, 8) in ``HDP_KEYS`` order;
``appnet_inputs`` hands the executor one column per key, as it does for
LIT's windows.  The compiled path runs the gates that read no state once over
whole streams and only the JK divider as a scan over words
(``kernels/netlist_exec.run_sequential``), which must stay bit-identical to
the gate-by-gate interpreter.
"""
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apps, circuits, dispatch, executor, obs
from repro.core import bitstream as bs
from repro.core.gates import Netlist, PIKind
from repro.core.plan import compile_plan
from repro.kernels import netlist_exec
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


def _free_output_net() -> Netlist:
    """A divider with outputs outside its state cone.

    ``Q`` is the divider of ``sc_scaled_div``; ``y``, the same MUX over
    streams alone, batches into one pass with it but reads no state. ``x``
    reads streams only, ``m`` drives the delay flip-flop ``R`` and is an
    output too, ``r = OR(R, A)`` reads the delayed bit and ``s`` reads
    ``r``, so the cone reaches past the gates that read state."""
    n = Netlist("div_free_outputs")
    a, b, c, d = (n.add_pi(p, value_key=p.lower()) for p in "ABCD")
    q = n.add_pi("Q", kind=PIKind.STATE)
    r = n.add_pi("R", kind=PIKind.STATE)
    qn = n.add_gate("NAND", [n.add_gate("NAND", [a, n.add_gate("NOT", [q], "Qb")], "n1"),
                             n.add_gate("NAND", [n.add_gate("NOT", [b], "Bb"), q], "n2")],
                    "Q_next")
    y = n.add_gate("NAND", [n.add_gate("NAND", [c, n.add_gate("NOT", [d], "Db")], "n3"),
                            n.add_gate("NAND", [n.add_gate("NOT", [a], "Ab"), d], "n4")],
                   "y")
    x = n.add_gate("NOR", [a, c], "x")
    m = n.add_gate("AND", [b, c], "m")
    n.bind_state(q, qn, init=0.0)
    n.bind_state(r, m, init=1.0)
    ro = n.add_gate("OR", [r, a], "r")
    n.set_outputs([qn, y, x, m, ro, n.add_gate("NOR", [ro, d], "s")])
    return n


def _oscillator() -> Netlist:
    n = Netlist("osc")
    q = n.add_pi("Q", kind=PIKind.STATE)
    n.add_gate("NOT", [q], "Qn")
    n.bind_state(q, "Qn", init=0.0)
    n.set_outputs(["Qn"])
    return n


def _exact_case(name: str):
    """(requests as (netlist, values, batch_shape), BL) of one case."""
    hdp = app_netlist("hdp")
    div = circuits.sc_scaled_div()
    if name.startswith("hdp-"):
        bl = int(name.split("-")[1])
        return [(hdp, apps.appnet_inputs("hdp", v=_queries(2**31 + bl, 333)),
                 None)], bl
    if name == "div":
        return [(div, {"a": np.linspace(0.1, 0.9, 7, dtype=np.float32),
                       "b": np.float32(0.3)}, None)], 256
    if name == "bank":                    # (5,) and scalar, merged and broadcast
        return [(hdp, apps.appnet_inputs("hdp", v=_queries(8, 5)), None),
                (div, {"a": np.float32(0.35), "b": np.float32(0.6)}, None)], 128
    if name == "free_outputs":
        rng = np.random.default_rng(9)
        return [(_free_output_net(),
                 {k: rng.uniform(0.1, 0.9, 6).astype(np.float32)
                  for k in "abcd"}, None)], 256
    assert name == "state_only"           # no stream PIs: n_words sizes it
    return [(_oscillator(), {}, (3,))], 128


@pytest.mark.parametrize("pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("case", ["hdp-64", "hdp-256", "div", "bank",
                                  "free_outputs", "state_only"])
def test_compiled_equals_interpreter_at_odd_batch(case, pallas):
    """The scan, its state cone split from the gates before it, gives the
    interpreter's streams bit for bit, per member of a merged bank too."""
    reqs, bl = _exact_case(case)
    keys = jax.random.split(jax.random.key(bl + 5), len(reqs))

    def run(backend, interpret=None):
        rs = [executor.ExecRequest(net, values, k, executor.ExecOptions(
                  bitstream_length=bl, backend=backend, batch_shape=shape,
                  interpret=interpret))
              for (net, values, shape), k in zip(reqs, keys)]
        return executor.run(rs) if len(rs) > 1 else [executor.run(rs[0])]

    got = run("compiled_pallas" if pallas else "compiled",
              True if pallas else None)
    want = run("reference")
    for g, w in zip(got, want):
        assert set(g) == set(w)
        for o in w:
            want_o = np.asarray(w[o])
            if case == "state_only":
                # The interpreter takes no batch shape for a plan without
                # streams: every element runs the same recurrence.
                want_o = np.broadcast_to(want_o, (3, *want_o.shape))
            np.testing.assert_array_equal(np.asarray(g[o]), want_o)
    if case.startswith("hdp"):
        (stream,) = got[0].values()
        values = bs.to_value(stream, bl)
        assert values.shape == (333,)
        assert len(np.unique(np.asarray(values))) > 10   # not a constant stream


def _scan_jaxpr(plan, pi_words, **kw):
    """The jaxpr of ``run_sequential`` for ``plan`` and the counters its
    trace added to ``obs.REGISTRY``."""
    def counts():
        c = obs.REGISTRY.snapshot()["counters"]
        return np.array([c.get("scan.hoisted_outputs", 0),
                         c.get("scan.serial_outputs", 0)])
    before = counts()
    jaxpr = jax.make_jaxpr(
        lambda pw: netlist_exec.run_sequential(plan, pw, **kw))(pi_words)
    return jaxpr, tuple(int(n) for n in counts() - before)


def test_scan_loop_holds_only_the_state_cone():
    """HDP's one state-dependent pass, the JK divider's MUX, is all the bit
    loop runs: the scan over words takes 2 boundary words (not the 11-row
    stream table), and a bit step extracts 2 bits and holds one MUX pass
    and the output packing (not 11 extractions and three MUX passes)."""
    plan = compile_plan(app_netlist("hdp"))
    pi_words = {n: jnp.zeros((4, 8), jnp.uint32)
                for n in plan.stream_pi_names()}
    assert len(pi_words) == 11
    jaxpr, (hoisted, serial) = _scan_jaxpr(plan, pi_words)
    assert (hoisted, serial) == (7, 1)
    (words,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    p = words.params
    assert len(words.invars) - p["num_consts"] - p["num_carry"] == 2
    (bits,) = [e for e in p["jaxpr"].jaxpr.eqns if e.primitive.name == "scan"]
    prims = Counter(e.primitive.name for e in bits.params["jaxpr"].jaxpr.eqns)
    assert prims["shift_right_logical"] == 2
    assert prims["or"] == 2                        # one MUX, one packing


def test_state_only_scan_hoists_nothing():
    plan = compile_plan(_oscillator())
    _, (hoisted, serial) = _scan_jaxpr(plan, {}, n_words=4, batch_shape=(3,))
    assert (hoisted, serial) == (0, 1)


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
