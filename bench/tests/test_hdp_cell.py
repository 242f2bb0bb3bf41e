"""CPU tests of the ``hdp_4m.frames`` cell and its plain reference,
``bench/apps/hdp.py``, at tiny sizes.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The reference's JK divider is checked on a hand-worked pair of streams;
the cell runs end to end through the harness on a 32x32 batch of queries,
and the control (the reference from bfloat16 inputs in the program's place)
must come out not correct.
"""
from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
CELL = "hdp_4m.frames"


def _packed(bits: set, n_bits: int = 64) -> np.ndarray:
    """One packed stream: bit ``t`` is bit ``t % 32`` of word ``t // 32``."""
    words = np.zeros(n_bits // 32, np.uint32)
    for t in bits:
        words[t // 32] |= np.uint32(1 << (t % 32))
    return words


def test_divider_on_hand_worked_streams():
    """``out_t = Q_t ? ~den_t : num_t``, ``Q_{t+1} = out_t``, ``Q_0 = 0``.

    Element 0, worked by hand: ``num`` sets Q at 1, 4, 31 and 63, ``den``
    clears it at 3, 6 and 36; Q is carried from word 0 into word 1 (set at
    31, cleared at 36) and ``den`` at 7, with Q already 0, does nothing.
    Element 1: ``num`` all ones and ``den`` all zeros give all ones."""
    hdp = harness.app_module("hdp")
    num = np.stack([_packed({1, 4, 31, 63}), _packed(set(range(64)))])
    den = np.stack([_packed({3, 6, 7, 36}), _packed(set())])
    out = np.asarray(hdp.divide(jnp.asarray(num), jnp.asarray(den)))
    np.testing.assert_array_equal(
        out, np.stack([_packed({1, 2, 4, 5, 31, 32, 33, 34, 35, 63}),
                       _packed(set(range(64)))]))


def test_same_seed_same_queries():
    hdp = harness.app_module("hdp")
    a, b, c = (hdp.frame_inputs(np.random.default_rng(s), 1, 40)
               for s in (2**31 + 5, 2**31 + 5, 6))
    assert a["v"].shape == (40, 8) and a["v"].dtype == np.float32
    assert np.array_equal(a["v"], b["v"])
    assert not np.array_equal(a["v"], c["v"])
    assert 0.1 <= a["v"].min() and a["v"].max() <= 0.9


@pytest.fixture(scope="module")
def run(tmp_path_factory) -> dict:
    """One untraced run of the cell, its batch cut to 32x32 queries."""
    root = tmp_path_factory.mktemp("hdp_tiny")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    os.symlink(ROOT / "src", root / "src")
    conf = root / "bench" / "configs" / "hdp_4m.json"
    c = json.loads(conf.read_text())
    c["frame"] = [32, 32]
    conf.write_text(json.dumps(c))
    return harness.run_cell(CELL, 2**31 + 1234567, 0.5, False,
                            require_tpu=False, root=root, log=lambda m: None)


def test_tiny_run_is_correct_and_reports_queries_per_second(run):
    assert run["correct"], run["checks"]
    assert run["checks"] == {"mismatched": {"value": 0, "limit": 0}}
    assert {"evals_per_s", "setup_s"} <= set(run["metrics"])
    rec = run["_record"]
    assert rec["frames"]["pixels"] == 32 * 32
    assert rec["frames"]["evals"] == rec["frames"]["completed"] * 32 * 32
    assert run["metrics"]["evals_per_s"]["value"] > 0


def test_control_is_not_correct(run):
    ctl = run["_record"]["_check"](True)
    assert ctl["mismatched"] > 0
    assert ctl["compared_elements"] == 6 * 32 * 32
