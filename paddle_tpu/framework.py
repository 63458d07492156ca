"""Graph IR: Program / Block / Operator / Variable.

TPU-native re-design of the reference's graph builder
(reference: python/paddle/fluid/framework.py:383,992,1443,2782 and
paddle/fluid/framework/framework.proto:43-184).  Instead of a protobuf
ProgramDesc interpreted op-by-op by a C++ executor, the Program here is a
lightweight Python IR that the executor lowers *wholesale* into a single
jitted XLA module (see paddle_tpu/core/lowering.py) — no per-op dispatch at
runtime, which is what lets XLA fuse the whole training step for the MXU.
"""
from __future__ import annotations

import collections
import contextlib
import copy
import itertools
import json
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from paddle_tpu import unique_name
from paddle_tpu.core import types as core_types
from paddle_tpu.core.types import VarType

__all__ = [
    "Variable",
    "Parameter",
    "Operator",
    "Block",
    "Program",
    "default_main_program",
    "default_startup_program",
    "program_guard",
    "name_scope",
    "grad_var_name",
    "in_dygraph_mode",
    "cpu_places",
    "CPUPlace",
    "TPUPlace",
]

GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    """reference: paddle/fluid/framework/grad_op_desc_maker.h (GradVarName)."""
    return name + GRAD_SUFFIX


# ---------------------------------------------------------------------------
# Places.  The reference models devices as a boost::variant Place
# (paddle/fluid/platform/place.h:79).  Here a Place selects a jax backend.
# ---------------------------------------------------------------------------
class Place:
    backend: Optional[str] = None  # None = jax default

    def __repr__(self):
        return type(self).__name__ + "()"


class CPUPlace(Place):
    backend = "cpu"


class TPUPlace(Place):
    """The TPU device place (the reference's CUDAPlace analog, place.h:58)."""

    backend = "tpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id


class CUDAPlace(TPUPlace):
    """Alias so reference-style scripts run unmodified; maps to the
    accelerator backend."""


class _DefaultPlace(Place):
    """Process-default device (no backend pin): Executor(place=None)."""

    backend = None


def cpu_places(device_count=None):
    return [CPUPlace()]


def cuda_places(device_ids=None):
    """reference: framework.py cuda_places — accelerator places.  On
    this build the accelerator is the TPU: returns one TPUPlace per
    visible chip (or per requested id)."""
    if device_ids is None:
        import jax

        # a CPU-only process still gets one place (reference scripts
        # index [0]); using it then fails loudly in Executor._device
        n = max(1, len([d for d in jax.devices() if d.platform != "cpu"]))
        device_ids = range(n)
    return [TPUPlace(int(i)) for i in device_ids]


def cuda_pinned_places(device_count=None):
    """reference: framework.py cuda_pinned_places — pinned host staging
    memory.  PJRT owns transfer staging on TPU; host-side places are
    plain CPUPlaces."""
    return [CPUPlace() for _ in range(device_count or 1)]


def is_compiled_with_cuda() -> bool:
    """reference: framework.py is_compiled_with_cuda.  This build
    targets TPU via XLA, never CUDA — always False (reference code
    gating on it falls back to its portable path, which is correct
    here)."""
    return False


# ---------------------------------------------------------------------------
# Dygraph mode switch (reference: framework.py:60-110)
# ---------------------------------------------------------------------------
_dygraph_tracer_ = None


def in_dygraph_mode() -> bool:
    return _dygraph_tracer_ is not None


def _dygraph_tracer():
    return _dygraph_tracer_


@contextlib.contextmanager
def _dygraph_guard(tracer):
    global _dygraph_tracer_
    prev = _dygraph_tracer_
    _dygraph_tracer_ = tracer
    try:
        yield
    finally:
        _dygraph_tracer_ = prev


# ---------------------------------------------------------------------------
# Variable
# ---------------------------------------------------------------------------
class Variable:
    """A named tensor in a Block (reference: framework.py:383).

    ``shape`` may contain -1 (unknown/batch dims); concrete shapes are bound
    at executor trace time.  LoD (ragged sequence) information is carried as
    an optional companion length tensor — see paddle_tpu/ops/sequence_ops.py
    for the padded+mask TPU encoding of the reference's LoDTensor
    (paddle/fluid/framework/lod_tensor.h:110).
    """

    def __init__(
        self,
        block: "Block",
        name: str,
        shape: Optional[Sequence[int]] = None,
        dtype: str = "float32",
        type: int = VarType.LOD_TENSOR,
        persistable: bool = False,
        stop_gradient: bool = False,
        lod_level: int = 0,
        is_data: bool = False,
        **kwargs,
    ):
        self.block = block
        self.name = name
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = core_types.canonical_dtype(dtype)
        self.type = type
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.lod_level = lod_level
        self.is_data = is_data
        # op that most recently produced this var (set by append_op)
        self.op: Optional["Operator"] = None

    # --- persistable participates in the executor's cached run-plan
    # (state_mut/ro/out derive from it), and the plan key is
    # (uid, version, op count, ...) — so a flag toggle AFTER a run (the
    # classic mark-before-save pattern) must bump the program version or
    # the stale plan would keep routing the var around the scope
    @property
    def persistable(self) -> bool:
        return self._persistable
    @persistable.setter
    def persistable(self, value) -> None:
        value = bool(value)
        if value == getattr(self, "_persistable", None):
            return  # idempotent re-mark: no analysis change, no recompile
        self._persistable = value
        prog = getattr(getattr(self, "block", None), "program", None)
        if prog is not None:
            prog.version += 1

    # --- sugar mirroring the reference Variable API ---
    def astype(self, dtype):
        from paddle_tpu.layers import tensor as ltensor

        return ltensor.cast(self, dtype)

    # --- dygraph surface (reference: framework.py:550 Variable.backward,
    # .numpy/.gradient on VarBase) ---
    def numpy(self):
        if getattr(self, "_dy_value", None) is None:
            raise RuntimeError("Variable.numpy() requires dygraph mode")
        import numpy as _np

        return _np.asarray(self._dy_value)

    def backward(self, backward_strategy=None):
        tracer = _dygraph_tracer()
        if tracer is None:
            raise RuntimeError("Variable.backward() requires dygraph mode")
        tracer.run_backward(self)

    def gradient(self):
        g = getattr(self, "_dy_grad", None)
        if g is None:
            return None
        import numpy as _np

        return _np.asarray(g)

    def clear_gradient(self):
        self._dy_grad = None

    @property
    def grad_name(self):
        return grad_var_name(self.name)

    def __repr__(self):
        return "Variable(name=%s, shape=%s, dtype=%s%s)" % (
            self.name,
            self.shape,
            self.dtype,
            ", persistable" if self.persistable else "",
        )

    __str__ = __repr__

    def _binary(self, other, op, reverse=False):
        from paddle_tpu.layers import math_helper

        return math_helper.binary_op(self, other, op, reverse)

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __rsub__(self, other):
        return self._binary(other, "elementwise_sub", reverse=True)

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    def __rtruediv__(self, other):
        return self._binary(other, "elementwise_div", reverse=True)

    def __neg__(self):
        from paddle_tpu.layers import tensor as ltensor

        return ltensor.scale(self, scale=-1.0)

    def to_dict(self):
        return {
            "name": self.name,
            "shape": list(self.shape) if self.shape is not None else None,
            "dtype": self.dtype,
            "type": int(self.type),
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "lod_level": self.lod_level,
            "is_data": self.is_data,
            "is_parameter": isinstance(self, Parameter),
            "trainable": getattr(self, "trainable", True),
        }


class Parameter(Variable):
    """A persistable, trainable Variable (reference: framework.py:3597)."""

    def __init__(self, block, name, shape, dtype, **kwargs):
        kwargs.setdefault("persistable", True)
        self.trainable = kwargs.pop("trainable", True)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.pop("regularizer", None)
        self.gradient_clip_attr = kwargs.pop("gradient_clip_attr", None)
        self.do_model_average = kwargs.pop("do_model_average", None)
        super().__init__(block, name, shape=shape, dtype=dtype, **kwargs)
        self.stop_gradient = not self.trainable


# ---------------------------------------------------------------------------
# Operator
# ---------------------------------------------------------------------------
class Operator:
    """An op node: type + named input/output var lists + attrs
    (reference: framework.py:992, framework.proto:105).

    Unlike the reference there is no OpProto validation against a C++
    registry; validation happens against the Python op registry
    (paddle_tpu/core/registry.py) which also holds the JAX kernel used at
    lowering time.
    """

    def __init__(
        self,
        block: "Block",
        type: str,
        inputs: Optional[Dict[str, List[str]]] = None,
        outputs: Optional[Dict[str, List[str]]] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ):
        self.block = block
        self.type = type
        self.inputs = {k: list(_names(v)) for k, v in (inputs or {}).items() if v is not None}
        self.outputs = {k: list(_names(v)) for k, v in (outputs or {}).items() if v is not None}
        self.attrs = dict(attrs or {})

    def input(self, slot: str) -> List[str]:
        return self.inputs.get(slot, [])

    def output(self, slot: str) -> List[str]:
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self):
        return [n for ns in self.inputs.values() for n in ns]

    @property
    def output_arg_names(self):
        return [n for ns in self.outputs.values() for n in ns]

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def _rename_input(self, old, new):
        for ns in self.inputs.values():
            for i, n in enumerate(ns):
                if n == old:
                    ns[i] = new

    def _rename_output(self, old, new):
        for ns in self.outputs.values():
            for i, n in enumerate(ns):
                if n == old:
                    ns[i] = new

    def to_dict(self):
        return {
            "type": self.type,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "attrs": _jsonable_attrs(self.attrs),
        }

    def __repr__(self):
        return "{%s} <- %s(%s)" % (
            ", ".join("%s=%s" % kv for kv in self.outputs.items()),
            self.type,
            ", ".join("%s=%s" % kv for kv in self.inputs.items()),
        )


def _names(v):
    if isinstance(v, (Variable, str)):
        v = [v]
    return [x.name if isinstance(x, Variable) else x for x in v]


def _jsonable_attrs(attrs):
    out = {}
    for k, v in attrs.items():
        if isinstance(v, np.ndarray):
            out[k] = {"__ndarray__": v.tolist(), "dtype": str(v.dtype)}
        elif isinstance(v, Block):
            out[k] = {"__block__": v.idx}
        elif isinstance(v, (np.integer,)):
            out[k] = int(v)
        elif isinstance(v, (np.floating,)):
            out[k] = float(v)
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------
class Block:
    """An ordered op list + var symbol table, possibly nested
    (reference: framework.py:1443, framework.proto:165)."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = collections.OrderedDict()
        self.ops: List[Operator] = []

    @property
    def parent_block(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    # --- var management ---
    def create_var(self, name=None, **kwargs) -> Variable:
        if name is None:
            name = unique_name.generate("_generated_var")
        if name in self.vars:
            return self.vars[name]
        var = Variable(self, name, **kwargs)
        self.vars[name] = var
        return var

    def create_parameter(self, name, shape, dtype, **kwargs) -> Parameter:
        param = Parameter(self, name, shape, dtype, **kwargs)
        # parameters live in the outermost (global) block, like the reference
        self.program.global_block().vars[name] = param
        if self is not self.program.global_block():
            self.vars[name] = param
        return param

    def var(self, name: str) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError("Variable %r not found in block %d" % (name, self.idx))
        return v

    def has_var(self, name: str) -> bool:
        return self._find_var_recursive(name) is not None

    def has_var_local(self, name: str) -> bool:
        return name in self.vars

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        blk = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        return None

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # --- op management ---
    def append_op(self, type, inputs=None, outputs=None, attrs=None) -> Operator:
        from paddle_tpu.core import registry

        if in_dygraph_mode():
            return _dygraph_tracer_.trace_op(type, inputs, outputs, attrs, block=self)
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.append(op)
        for ns in op.outputs.values():
            for n in ns:
                if n in self.vars:
                    self.vars[n].op = op
        registry.infer_shape(op, self)
        return op

    def _insert_op(self, index, type, inputs=None, outputs=None, attrs=None) -> Operator:
        from paddle_tpu.core import registry

        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(index, op)
        registry.infer_shape(op, self)
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None) -> Operator:
        return self._insert_op(0, type, inputs, outputs, attrs)

    def _remove_op(self, index):
        del self.ops[index]

    def to_dict(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": [v.to_dict() for v in self.vars.values()],
            "ops": [op.to_dict() for op in self.ops],
        }


def _program_uid(obj) -> int:
    """Monotonic identity for compile-cache keys (never-reused, unlike
    ``id()``).  Programs get theirs at construction; any other cache
    participant (e.g. a CompiledProgram wrapper) is stamped lazily on
    first use."""
    uid = getattr(obj, "_ptpu_uid", None)
    if uid is None:
        uid = next(Program._uid_counter)
        try:
            obj._ptpu_uid = uid
        except AttributeError:
            return id(obj)  # __slots__ object: fall back to id
    return uid


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------
class Program:
    """A list of Blocks; block 0 is global (reference: framework.py:2782).

    ``version`` is bumped on structural edits and participates in the
    executor's compile-cache key, together with ``_ptpu_uid`` — a
    process-monotonic program identity.  The executor used to key on
    ``id(program)``, but CPython reuses ids after GC, so two programs
    alive at different times could alias one jit-cache entry; the uid
    can never collide.
    """

    _uid_counter = itertools.count(1)

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self.version = 0
        self.random_seed = 0
        self._op_role = "forward"
        self._seed_counter = 0
        self._ptpu_uid = next(Program._uid_counter)

    # --- block management ---
    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx=None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        blk = Block(self, len(self.blocks), parent)
        self.blocks.append(blk)
        self.current_block_idx = blk.idx
        return blk

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def block(self, idx) -> Block:
        return self.blocks[idx]

    @property
    def num_blocks(self):
        return len(self.blocks)

    def all_parameters(self):
        return self.global_block().all_parameters()

    def list_vars(self):
        for blk in self.blocks:
            yield from blk.vars.values()

    def next_seed(self) -> int:
        """Deterministic per-op RNG seed derived from program.random_seed."""
        self._seed_counter += 1
        return (self.random_seed * 1000003 + self._seed_counter) & 0x7FFFFFFF

    def clone(self, for_test: bool = False) -> "Program":
        """reference: framework.py Program.clone — for_test drops optimize
        ops and switches is_test attrs."""
        p = copy.deepcopy(self)
        if for_test:
            for blk in p.blocks:
                kept = []
                for op in blk.ops:
                    role = op.attrs.get("op_role", "forward")
                    if for_test and role in ("backward", "optimize"):
                        continue
                    if "is_test" in op.attrs:
                        op.attrs["is_test"] = True
                    if op.type == "dropout":
                        op.attrs["is_test"] = True
                    kept.append(op)
                blk.ops = kept
        p.version += 1
        # deepcopy duplicated the source's uid; a clone is a DISTINCT
        # program and must never share a compile-cache identity with it
        p._ptpu_uid = next(Program._uid_counter)
        return p

    # --- serialization (the reference's ProgramDesc protobuf round-trip,
    # framework.proto:184; here a stable JSON encoding) ---
    def to_json(self) -> str:
        payload = {
            "version": 1,
            "random_seed": self.random_seed,
            "blocks": [b.to_dict() for b in self.blocks],
        }
        # distributed lookup-table metadata (layers.embedding
        # is_distributed=True) must survive serde — without it a
        # saved/loaded huge-table program can no longer prefetch/push
        dist = getattr(self, "_distributed_tables", None)
        if dist:
            payload["distributed_tables"] = dist
        return json.dumps(payload)

    @staticmethod
    def from_json(text: str) -> "Program":
        data = json.loads(text)
        prog = Program()
        prog.random_seed = data.get("random_seed", 0)
        if data.get("distributed_tables"):
            prog._distributed_tables = data["distributed_tables"]
        prog.blocks = []
        for bd in data["blocks"]:
            blk = Block(prog, bd["idx"], bd["parent_idx"])
            prog.blocks.append(blk)
        for bd, blk in zip(data["blocks"], prog.blocks):
            for vd in bd["vars"]:
                cls = Parameter if vd.pop("is_parameter", False) else Variable
                trainable = vd.pop("trainable", True)
                name = vd.pop("name")
                shape = vd.pop("shape")
                if cls is Parameter:
                    v = Parameter(blk, name, shape, vd.pop("dtype"), trainable=trainable, **vd)
                else:
                    v = Variable(blk, name, shape=shape, **vd)
                blk.vars[name] = v
            for od in bd["ops"]:
                attrs = {}
                for k, v in od["attrs"].items():
                    if isinstance(v, dict) and "__ndarray__" in v:
                        attrs[k] = np.array(v["__ndarray__"], dtype=v["dtype"])
                    elif isinstance(v, dict) and "__block__" in v:
                        attrs[k] = prog.blocks[v["__block__"]]
                    else:
                        attrs[k] = v
                blk.ops.append(Operator(blk, od["type"], od["inputs"], od["outputs"], attrs))
        return prog

    def __repr__(self):
        lines = []
        for blk in self.blocks:
            lines.append("-- block %d (parent %d) --" % (blk.idx, blk.parent_idx))
            for v in blk.vars.values():
                lines.append("  " + repr(v))
            for op in blk.ops:
                lines.append("  " + repr(op))
        return "\n".join(lines)

    __str__ = __repr__


# ---------------------------------------------------------------------------
# Default program singletons & guards (reference: framework.py:3692-3725)
# ---------------------------------------------------------------------------
_main_program_ = Program()
_startup_program_ = Program()


def default_main_program() -> Program:
    return _main_program_


def default_startup_program() -> Program:
    return _startup_program_


def switch_main_program(program: Program) -> Program:
    global _main_program_
    prev, _main_program_ = _main_program_, program
    return prev


def switch_startup_program(program: Program) -> Program:
    global _startup_program_
    prev, _startup_program_ = _startup_program_, program
    return prev


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    prev_main = switch_main_program(main_program)
    prev_startup = None
    if startup_program is not None:
        prev_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_startup is not None:
            switch_startup_program(prev_startup)


@contextlib.contextmanager
def name_scope(prefix: str):
    with unique_name.guard_prefix(prefix):
        yield


@contextlib.contextmanager
def op_role_guard(program: Program, role: str):
    prev = program._op_role
    program._op_role = role
    try:
        yield
    finally:
        program._op_role = prev
