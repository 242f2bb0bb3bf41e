"""Compile-only checks of the Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one kernel of the main path at
real widths for a ``v5e:2x2`` topology that is described, not attached (the
TPU compiler ships with libtpu).  That catches what interpret mode accepts
and the chip's compiler refuses — blocks off the (8, 128) tiling, rank-1
blocks, programs that do not fit HBM.  The topology is described inside a
module fixture, never at import: only one process may load libtpu, and
every pytest-xdist worker imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import apps, executor
from repro.core.appnet import APP_NETLISTS
from repro.core.plan import compile_plan
from repro.kernels.packed_logic import packed_logic
from repro.kernels.plan_megakernel import combinational_megakernel
from repro.kernels.sng import sng_words

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("op,shape", [("mux", (76800, 32)),
                                      ("and", (1024, 512))])
def test_packed_logic_compiles_lane_dense(one_chip, op, shape):
    n_in = 3 if op == "mux" else 2
    c = _compile(lambda *xs: packed_logic(op, *xs, interpret=False),
                 *[_u32(shape, one_chip)] * n_in)
    assert "packed_logic" in c.as_text()
    # A frame of 32-word streams stays lane-dense: no padded relayout copy.
    assert c.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("n,b", [
    (406, 76800),       # LIT's stream table over a 320x240 frame
    (8 * 406, 1),       # a served 8-slot LIT bank, one pixel per request
])
def test_sng_words_compiles_at_stream_table_size(one_chip, n, b):
    w = 32                                              # BL=1024
    c = _compile(lambda s, t: sng_words(s, t, w, use_pallas=True,
                                        interpret=False),
                 _u32((n,), one_chip), _u32((n, b), one_chip))
    assert "sng_words" in c.as_text()
    ma = c.memory_analysis()
    # Lane-dense: at most the row tiles' few percent of padding, never the
    # 4x of 32 words padded out to 128 lanes.
    assert n * b * w * 4 <= ma.output_size_in_bytes <= 1.05 * n * b * w * 4
    # No temp: the kernel's word-major output IS the result buffer, and the
    # final axis swap compiles to a bitcast, not a copy.
    assert ma.temp_size_in_bytes == 0


def test_megakernel_compiles_for_lit_plan(one_chip):
    plan = compile_plan(APP_NETLISTS["lit"]())
    env = {pi.name: _u32((4096, 32), one_chip)
           for pi, s in zip(plan.pis, plan.pi_slots) if s >= 0}
    assert len(env) == 406 and plan.max_live == 567
    c = _compile(lambda e: combinational_megakernel(plan, e, interpret=False),
                 env)
    assert "plan_megakernel" in c.as_text()


@pytest.mark.parametrize("app,inputs", [
    ("ol", {"p": np.zeros((256, 16, 6), np.float32)}),
    ("lit", {"a": np.zeros((4096, 81), np.float32)}),
])
def test_pallas_app_program_keeps_layouts(one_chip, app, inputs):
    # SNG writes word-major and the passes read lane-dense tiles: every axis
    # swap between them must compile to a bitcast, never a transpose or a
    # relayout copy of a stream.
    net = APP_NETLISTS[app]()
    values = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.float32,
                                       sharding=one_chip),
        apps.appnet_inputs(app, **inputs))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    opts = executor.ExecOptions(backend="compiled_pallas",
                                bitstream_length=1024, interpret=False)
    text = _compile(lambda v, k: executor.run(executor.ExecRequest(
        net, v, k, opts)), values, key).as_text()
    assert "sng_words" in text and "packed_logic" in text
    assert not re.search(r"= \S+ (transpose|copy)\(", text)
