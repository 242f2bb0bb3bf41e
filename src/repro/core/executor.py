"""Netlist execution facade: compiled fused plans with a gate-by-gate reference.

Bridges the structural view (circuits.py netlists, used for scheduling and
cost) and the value view (sc_ops.py): every netlist can be *run* and its
output streams decoded, so tests can assert that the scheduled circuits
compute what the paper says they compute — including sequential (stateful)
circuits like the Gaines divider, and under injected bitflips (Table 4).

Two backends share identical semantics (bit-for-bit):

  * ``"compiled"`` (default): the netlist is lowered once by the staged
    compiler pipeline (``core/compiler/``, fronted by ``core/plan.py``) into
    leveled, type-batched fused passes and executed by
    ``kernels/netlist_exec.py`` inside a single jit — stream generation,
    logic, fault injection and state recurrence all in one XLA program.
    ``"compiled_pallas"`` additionally routes each fused pass through the
    packed-logic Pallas kernel.
  * ``"reference"``: the original Python interpreter, one dispatch per gate.
    It is the oracle the compiled path is tested against, and the fallback
    for debugging new circuits.

Binary netlists execute on packed test-vector words: lane ``t`` of the packed
words is test vector ``t``, so one call evaluates 32*W random input
combinations at once.

Orthogonal to the backend, ``key_mode`` selects the stream-generation key
discipline (both backends honor it identically): ``"batched"`` (default)
generates every PI stream of a plan — or a whole bank — in ONE fused
threshold+pack pass over the plan's stream table; ``"legacy"`` reproduces
the pre-batching per-PI threefry splits bit-exactly.

The canonical entry point is ``run()`` over ``ExecRequest``s: one request
(netlist or prebuilt plan + PI values + PRNG key + frozen ``ExecOptions``)
executes standalone, a sequence merges into one bank-level program, and
``run(requests, template=bank)`` binds slot-aligned requests onto a padded
bank template (the serving path — ``device=`` places the batch on a specific
JAX device, ``donate=`` consumes the engine-owned key rows).  The historic
``execute*`` functions remain as thin shims that build ``ExecRequest``s and
delegate to ``run()``; outputs are bit-identical (pinned by tests).

This module is a *facade*: the implementation is layered as

  * ``core/streams.py``  — PI stream generation (both key disciplines);
  * ``core/dispatch.py`` — jit boundary, value packing/normalization, bank
    execution, the reference interpreter;
  * ``core/exec_api.py`` — ``ExecOptions``/``ExecRequest``, ``run()``, and
    the historic ``execute*`` shims.

Every name importable from here before the split still is.
"""
from __future__ import annotations

from .dispatch import (_BANK_STATIC, _as_f32, _check_fault_args,  # noqa: F401
                       _check_modes, _dispatch, _dispatch_binary,
                       _dispatch_many, _execute_bank, _execute_bank_donating,
                       _execute_bank_impl, _execute_binary_compiled,
                       _execute_compiled, _execute_reference, _is_host_scalar,
                       _key_data_host, _normalize_active,
                       _normalize_batch_shapes, _normalize_keys,
                       _pack_values_seq, _plan_for, _restrict, _stack_keys,
                       _unpack_values_seq, execute_bank,
                       generate_bank_streams)
from .faults import FaultModel, apply_faults  # noqa: F401
from . import obs  # noqa: F401  (re-export: executor.obs.Trace etc.)
from .exec_api import (_MANY_TAIL, ExecOptions, ExecRequest,  # noqa: F401
                       _common_options, _many_shim, _many_tail, _run_many,
                       _run_one, _run_template, execute,
                       execute_binary, execute_many, execute_value,
                       execute_value_many, precompile, run)
from .streams import (_BACKENDS, _KEY_MODES, DEFAULT_BACKEND,  # noqa: F401
                      DEFAULT_KEY_MODE, _gen_bank_streams, _gen_pi_streams,
                      _pi_shape, _stack_table_values)

__all__ = [
    "DEFAULT_BACKEND", "DEFAULT_KEY_MODE", "ExecOptions", "ExecRequest",
    "FaultModel", "execute", "execute_bank", "execute_binary",
    "execute_many", "execute_value", "execute_value_many",
    "generate_bank_streams", "obs", "precompile", "run",
]
