"""Quickstart: the paper's stochastic in-memory computing stack end to end.

  PYTHONPATH=src python examples/quickstart.py

1. stochastic arithmetic on packed bitstreams (Fig. 4/5 semantics);
2. Algorithm 1 scheduling of a netlist onto a 2T-1MTJ subarray;
3. the [n, m] Stoch-IMC architecture cost model (Table 3 machinery);
4. one paper application (object location) in exact / SC / binary form.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import apps, bitstream as bs, circuits
from repro.core.appnet import APP_NETLISTS
from repro.core.arch import StochIMCConfig, evaluate_stoch_imc
from repro.core.executor import execute_value
from repro.core.scheduler import schedule

enable_compile_cache()
key = jax.random.key(0)
BL = 1024

print("== 1. stochastic arithmetic on packed bitstreams ==")
a, b = 0.3, 0.6
sa = bs.generate(jax.random.key(1), jnp.float32(a), BL)
sb = bs.generate(jax.random.key(2), jnp.float32(b), BL)
print(f"  AND(a,b):  {float(bs.to_value(sa & sb, BL)):.3f}   (a*b = {a * b})")
ca, cb = bs.generate_correlated(key, [jnp.float32(a), jnp.float32(b)], BL)
print(f"  XOR corr:  {float(bs.to_value(ca ^ cb, BL)):.3f}   (|a-b| = {abs(a - b)})")

print("\n== 2. Algorithm 1: schedule the scaled-adder netlist ==")
net = circuits.sc_scaled_add()
sch = schedule(net, n_lanes=256)
print(f"  logic cycles: {sch.logic_cycles} (Fig. 7(b): 4), "
      f"array: {sch.n_rows}x{sch.n_cols} (Table 2: 256x7)")
out = execute_value(net, {"a": jnp.float32(a), "b": jnp.float32(b)}, key, BL)
print(f"  executed value: {float(out['out']):.3f}  ((a+b)/2 = {(a + b) / 2})")

print("\n== 3. [16,16] Stoch-IMC architecture cost (one OL evaluation) ==")
cfg = StochIMCConfig()
ol = APP_NETLISTS["ol"]()
cost = evaluate_stoch_imc(ol, schedule(ol, n_lanes=1), cfg)
print(f"  cycles={cost.total_cycles} (incl. {cost.accumulation_cycles} "
      f"n+m accumulation), energy={cost.total_energy_j:.3e} J")

print("\n== 4. object-location application, three ways ==")
p = np.random.default_rng(0).random((4, 6)) * 0.5 + 0.5
print("  exact:     ", np.round(apps.ol_exact(p), 4))
print("  stochastic:", np.round(np.asarray(apps.ol_stochastic(key, p, BL)), 4))
print("  binary-8b: ", np.round(apps.ol_binary8(np.random.default_rng(1), p), 4))
print("  stochastic @20% bitflips:",
      np.round(np.asarray(apps.ol_stochastic(key, p, BL, bitflip_rate=0.2)), 4))
