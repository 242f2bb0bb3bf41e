"""``chip_smoke.py``'s phases, run in-process on the CPU at a tiny size.

The script itself needs a TPU; here its phase functions run with Pallas in
interpret mode (``interpret=True``), so a phase that would fail on the chip
for a reason of logic — a wrong reference, a declined megakernel, a served
ticket that differs from its standalone run — fails here first.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro import compile_cache  # noqa: E402


@pytest.mark.parametrize("app", ["lit", "ol", "hdp", "kde"])
def test_batch_phase_bit_identical_tiny(app):
    import jax
    rng = np.random.default_rng(3)
    inputs, exact = chip_smoke.app_inputs(app, rng, frame=(4, 8), ol_tiles=2,
                                          hdp_queries=16)
    rep = chip_smoke.run_batch_app(app, inputs, exact, jax.random.key(3),
                                   interpret=True, on_chip=False)
    assert sorted(rep) == sorted(chip_smoke.BACKENDS)
    assert all(r["mae"] <= chip_smoke.TOLERANCE[app] for r in rep.values())


def test_served_phase_tiny():
    rep = chip_smoke.phase_served(5, bursts=((1, 1),))
    st = rep["stats"]
    assert st["n_requests"] == 2 and st["failed_requests"] == 0
    assert st["retries"] == st["quarantines"] == st["shed_requests"] == 0


def test_main_refuses_cpu_without_verdict(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: "off")
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "no TPU chip found" in out and '"ok"' not in out


def _compile_in_child(env_extra, code=""):
    """Enable the cache and compile once in a fresh CPU-only process;
    returns the directory ``enable_compile_cache`` reported."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **env_extra)
    prog = ("from pathlib import Path\n"
            "from repro import compile_cache\n" + code +
            "import jax, jax.numpy as jnp\n"
            "print(compile_cache.enable_compile_cache())\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.arange(4)).block_until_ready()\n")
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    return res.stdout.strip().splitlines()[-1]


def test_compile_cache_uses_env_dir(tmp_path):
    d = tmp_path / "from_env"
    used = _compile_in_child({"JAX_COMPILATION_CACHE_DIR": str(d)})
    assert used == str(d)
    assert any(p.name.endswith("-cache") for p in d.iterdir())


def test_compile_cache_default_is_fixed_ignored_checkout_path(tmp_path):
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    d = tmp_path / "default"
    used = _compile_in_child(
        {}, f"compile_cache.DEFAULT_DIR = Path({str(d)!r})\n")
    assert used == str(d)
    assert any(p.name.endswith("-cache") for p in d.iterdir())
