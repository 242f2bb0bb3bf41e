"""Pallas TPU kernels for the compute hot-spots the paper optimizes, each
with a pure-jnp oracle (exact-equality or allclose tests in tests/):

  sng            fused stochastic number generation (the in-memory BtoS step)
  packed_logic   bit-parallel boolean algebra over packed uint32 lanes
  netlist_exec   fused execution of compiled netlist plans (core/plan.py)
  plan_megakernel  a whole plan as one fused kernel over VMEM-pooled streams
  popcount_tree  hierarchical StoB accumulation (Fig. 8's local/global tree)
  sc_matmul      popcount(AND) stochastic matrix multiply w/ in-kernel SNG
"""
from . import common, netlist_exec, ops, ref
from .packed_logic import packed_logic
from .popcount_tree import popcount_hier
from .sc_matmul import sc_matmul
from .sng import lane_seeds, sng_pack, sng_words

__all__ = ["common", "netlist_exec", "ops", "ref", "packed_logic",
           "popcount_hier", "sc_matmul", "lane_seeds", "sng_pack", "sng_words"]
