"""Program-as-data IR.

Rebuilds the semantics of the reference's fluid graph representation
(reference: python/paddle/v2/fluid/framework.py — ``Program:711``,
``Block:567``, ``Operator:310``, ``Variable:93``; and the protobuf
schema paddle/framework/framework.proto:33-145) as native Python
dataclass-style objects.  Unlike the reference there is no C++
``ProgramDesc`` mirror: the Python IR *is* the program, and the
Executor lowers it straight to XLA via JAX tracing.  A protobuf-free
``to_dict``/``from_dict`` serialization replaces the proto wire format.
"""

from __future__ import annotations

import collections
import contextlib
import copy
import hashlib
import json
import re
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

# ---------------------------------------------------------------------------
# Places (reference: paddle/platform/place.h:24-98).  On TPU there is no
# per-op placement decision — a Place selects which jax backend the
# Executor compiles for.
# ---------------------------------------------------------------------------


class Place:
    _backend = None

    def device(self):
        """The jax device this place pins work to; None = jax's default
        device (whatever backend the process runs on)."""
        if self._backend is None:
            return None
        import jax

        return jax.devices(self._backend)[0]

    def __repr__(self):
        return type(self).__name__ + "()"

    def __eq__(self, other):
        return type(self) is type(other)

    def __hash__(self):
        return hash(type(self).__name__)


class CPUPlace(Place):
    _backend = "cpu"


class TPUPlace(Place):
    """The default-backend place: work runs on jax's default device —
    the TPU on a TPU host, the CPU under ``JAX_PLATFORMS=cpu`` (the
    tests).  It does NOT assert a TPU; measurement paths (chip_smoke.py,
    perf/run.py) check ``jax.devices()[0].platform`` themselves."""

    _backend = None  # None = jax default backend


def device_record() -> dict:
    """The device work runs on by default, as jax reports it — the
    record every printed result and GET /health carries."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


# GPUPlace alias kept for API familiarity with the reference; it selects
# the default accelerator just like TPUPlace.
CUDAPlace = TPUPlace
GPUPlace = TPUPlace


# ---------------------------------------------------------------------------
# Data types.  (reference: framework.proto DataType enum)
# ---------------------------------------------------------------------------

_DTYPE_CANON = {
    "bool": "bool",
    "int8": "int8",
    "uint8": "uint8",
    "int16": "int16",
    "int32": "int32",
    "int64": "int64",
    "float16": "float16",
    "bfloat16": "bfloat16",
    "float32": "float32",
    "float64": "float64",
}


def convert_dtype(dtype) -> str:
    """Canonicalize a dtype spec (str / np.dtype / jnp dtype) to a string."""
    if dtype is None:
        return "float32"
    if isinstance(dtype, str):
        name = dtype
    else:
        name = np.dtype(dtype).name if not hasattr(dtype, "name") else dtype.name
    name = {"float": "float32", "double": "float64", "int": "int32"}.get(name, name)
    if name not in _DTYPE_CANON:
        raise ValueError(f"unsupported dtype {dtype!r}")
    return name


def is_float_dtype(dtype: str) -> bool:
    return convert_dtype(dtype) in ("float16", "bfloat16", "float32", "float64")


# ---------------------------------------------------------------------------
# Unique names (reference: fluid framework.py unique_name)
# ---------------------------------------------------------------------------


class _UniqueNameGenerator:
    def __init__(self):
        self.ids = collections.defaultdict(int)

    def __call__(self, key: str) -> str:
        tmp = self.ids[key]
        self.ids[key] += 1
        return f"{key}_{tmp}"


_name_gen = _UniqueNameGenerator()


def unique_name(key: str) -> str:
    return _name_gen(key)


GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


# ---------------------------------------------------------------------------
# Variable  (reference: fluid framework.py:93; framework/var_desc.h)
# ---------------------------------------------------------------------------


class VarType:
    LOD_TENSOR = "lod_tensor"
    SELECTED_ROWS = "selected_rows"
    LOD_TENSOR_ARRAY = "lod_tensor_array"
    STEP_SCOPES = "step_scopes"
    RAW = "raw"


class Variable:
    def __init__(
        self,
        block: "Block",
        name: Optional[str] = None,
        shape: Optional[Sequence[int]] = None,
        dtype="float32",
        lod_level: int = 0,
        persistable: bool = False,
        stop_gradient: bool = False,
        type: str = VarType.LOD_TENSOR,
        initializer=None,
    ):
        self.block = block
        self.name = name if name is not None else unique_name("_generated_var")
        # unknown dims may be given as None (normalized to -1)
        self.shape = (
            tuple(-1 if s is None else int(s) for s in shape)
            if shape is not None else None
        )
        self.dtype = convert_dtype(dtype)
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.type = type
        # set lazily by layers that want an init op appended to startup
        self.initializer = initializer
        # optional sharding hint (PartitionSpec-shaped tuple) for
        # parallel strategies; set via ParamAttr(shard=...)
        self.dist_spec = None

    # convenience mirroring the reference API
    @property
    def ndim(self):
        return len(self.shape) if self.shape is not None else None

    def __repr__(self):
        return (
            f"Variable(name={self.name!r}, shape={self.shape}, dtype={self.dtype},"
            f" lod_level={self.lod_level}, persistable={self.persistable})"
        )

    def to_dict(self):
        return {
            "name": self.name,
            "shape": list(self.shape) if self.shape is not None else None,
            "dtype": self.dtype,
            "lod_level": self.lod_level,
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "type": self.type,
            "is_parameter": isinstance(self, Parameter),
            "trainable": getattr(self, "trainable", None),
        }


class Parameter(Variable):
    """A trainable, persistable variable (reference: fluid framework.py
    ``Parameter``; paddle/parameter/Parameter.h:60 in the legacy stack)."""

    def __init__(self, block, shape, dtype, **kwargs):
        self.trainable = kwargs.pop("trainable", True)
        self.regularizer = kwargs.pop("regularizer", None)
        self.gradient_clip = kwargs.pop("gradient_clip", None)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        super().__init__(
            block, shape=shape, dtype=dtype, persistable=True, **kwargs
        )


# ---------------------------------------------------------------------------
# Operator  (reference: fluid framework.py:310; framework/op_desc.h)
# ---------------------------------------------------------------------------


def _as_name_list(v) -> List[str]:
    if v is None:
        return []
    if isinstance(v, (list, tuple)):
        return [x.name if isinstance(x, Variable) else str(x) for x in v]
    return [v.name if isinstance(v, Variable) else str(v)]


class _AttrDict(dict):
    """Op attrs that version-bump the owning program on mutation, so the
    executor's compile cache can detect in-place attr edits (e.g.
    flipping ``is_test`` by hand) without rehashing every run."""

    __slots__ = ("_op",)

    def __init__(self, op, mapping=None):
        super().__init__(mapping or {})
        self._op = op

    def _touch(self):
        block = getattr(self._op, "block", None)
        prog = getattr(block, "program", None) if block is not None else None
        if prog is not None:
            prog._version = getattr(prog, "_version", 0) + 1

    def __setitem__(self, k, v):
        super().__setitem__(k, v)
        self._touch()

    def __delitem__(self, k):
        super().__delitem__(k)
        self._touch()

    def update(self, *a, **kw):
        super().update(*a, **kw)
        self._touch()

    def pop(self, *a):
        out = super().pop(*a)
        self._touch()
        return out

    def setdefault(self, k, default=None):
        out = super().setdefault(k, default)
        self._touch()
        return out

    def clear(self):
        super().clear()
        self._touch()

    def popitem(self):
        out = super().popitem()
        self._touch()
        return out

    def __ior__(self, other):  # ``attrs |= {...}`` bypasses update()
        super().update(other)
        self._touch()
        return self

    def __deepcopy__(self, memo):
        new = _AttrDict.__new__(_AttrDict)
        dict.__init__(new)
        memo[id(self)] = new  # before the _op recursion re-enters us
        new._op = copy.deepcopy(self._op, memo)
        for k, v in self.items():
            dict.__setitem__(new, k, copy.deepcopy(v, memo))
        return new


class Operator:
    def __init__(
        self,
        block: "Block",
        type: str,
        inputs: Optional[Dict[str, Any]] = None,
        outputs: Optional[Dict[str, Any]] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ):
        self.block = block
        self.type = type
        self.inputs: Dict[str, List[str]] = {
            k: _as_name_list(v) for k, v in (inputs or {}).items()
        }
        self.outputs: Dict[str, List[str]] = {
            k: _as_name_list(v) for k, v in (outputs or {}).items()
        }
        self._attrs: Dict[str, Any] = _AttrDict(self, attrs or {})
        if _RECOMPUTE_SEG[0] is not None:
            self._attrs["__recompute_seg__"] = _RECOMPUTE_SEG[0]
            # stable per-op key index: the backward replay may run a
            # PRUNED subset of the segment (loss-relevant ops only), so
            # positional key splitting would shift the stream — each
            # op folds its own fixed index into the segment key instead
            _RECOMPUTE_OP_IDX[0] += 1
            self._attrs["__seg_rng_idx__"] = _RECOMPUTE_OP_IDX[0]
        # Run registry-side checks/infer-shape at append time, like the
        # reference's compile-time InferShape (framework/op_desc.cc).
        from paddle_tpu import registry

        info = registry.OpRegistry.get(type, none_ok=True)
        if info is not None and info.infer_shape is not None:
            try:
                info.infer_shape(self, block)
            except registry.SkipInferShape:
                pass

    def input(self, slot: str) -> List[str]:
        return self.inputs.get(slot, [])

    def output(self, slot: str) -> List[str]:
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self) -> List[str]:
        return [n for ns in self.inputs.values() for n in ns]

    @property
    def output_arg_names(self) -> List[str]:
        return [n for ns in self.outputs.values() for n in ns]

    @property
    def attrs(self) -> Dict[str, Any]:
        return self._attrs

    @attrs.setter
    def attrs(self, mapping):
        # wholesale rebinds (op.attrs = {...}) must stay version-tracked,
        # or the executor compile cache silently reuses stale executables
        if isinstance(mapping, _AttrDict) and mapping._op is self:
            self._attrs = mapping
        else:
            self._attrs = _AttrDict(self, dict(mapping or {}))
        self._attrs._touch()

    def attr(self, name: str, default=None):
        return self.attrs.get(name, default)

    def to_dict(self):
        def _attr_ser(v):
            if isinstance(v, Block):
                return {"__block__": v.idx}
            if isinstance(v, np.ndarray):
                return {"__ndarray__": v.tolist(), "dtype": str(v.dtype)}
            if (isinstance(v, list) and v
                    and all(isinstance(o, Operator) for o in v)):
                # recompute_segment_grad __seg_ops__: one-way dump
                # (backward ops are pruned from inference exports)
                return {"__seg_ops__": [o.to_dict() for o in v]}
            return v

        return {
            "type": self.type,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "attrs": {k: _attr_ser(v) for k, v in self.attrs.items()},
        }

    def __repr__(self):
        ins = ", ".join(f"{k}={v}" for k, v in self.inputs.items())
        outs = ", ".join(f"{k}={v}" for k, v in self.outputs.items())
        return f"{{{self.type}: ({ins}) -> ({outs})}}"


# ---------------------------------------------------------------------------
# Block  (reference: fluid framework.py:567; framework/block_desc.h:37)
# ---------------------------------------------------------------------------


class Block:
    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = collections.OrderedDict()
        self.ops: List[Operator] = []

    @property
    def parent(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    # --- variables ---------------------------------------------------------

    def create_var(self, **kwargs) -> Variable:
        var = Variable(self, **kwargs)
        self.vars[var.name] = var
        return var

    def create_parameter(self, shape, dtype, **kwargs) -> Parameter:
        # parameters always live in the root block (reference:
        # fluid framework.py global_block parameter placement)
        global_block = self.program.blocks[0]
        param = Parameter(global_block, shape, dtype, **kwargs)
        global_block.vars[param.name] = param
        return param

    def var(self, name: str) -> Variable:
        v = self.find_var(name)
        if v is None:
            raise KeyError(f"variable {name!r} not found in block {self.idx}")
        return v

    def find_var(self, name: str) -> Optional[Variable]:
        """Parent-chain lookup (reference: framework/scope.h:38 FindVar)."""
        b: Optional[Block] = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent
        return None

    def has_var(self, name: str) -> bool:
        return self.find_var(name) is not None

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # --- ops ---------------------------------------------------------------

    def append_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.append(op)
        return op

    def prepend_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        return op

    def insert_op(self, index, type, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(index, op)
        return op

    def to_dict(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": {k: v.to_dict() for k, v in self.vars.items()},
            "ops": [op.to_dict() for op in self.ops],
        }


# ---------------------------------------------------------------------------
# Program  (reference: fluid framework.py:711; framework/program_desc.h)
# ---------------------------------------------------------------------------


class Program:
    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self.seed: Optional[int] = None  # program-level RNG seed
        self._version = 0  # bumped on in-place op-attr mutation

    # --- block management --------------------------------------------------

    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def create_block(self, parent_idx: Optional[int] = None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        return b

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def all_parameters(self) -> List[Parameter]:
        return self.global_block().all_parameters()

    # --- serialization / identity ------------------------------------------

    def to_dict(self):
        return {
            "blocks": [b.to_dict() for b in self.blocks],
            "seed": self.seed,
        }

    def to_string(self, throw_on_error: bool = False) -> str:
        return json.dumps(self.to_dict(), indent=2, default=str)

    __str__ = to_string

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Program":
        """Rebuild a Program from ``to_dict`` output (the protobuf-free
        wire format used by save_inference_model's __model__.json and
        ``paddle lint <program.json>``)."""
        p = Program.__new__(Program)
        p.blocks = []
        p.current_block_idx = 0
        p.seed = d.get("seed")
        p._version = 0
        for bd in d["blocks"]:
            b = Block(p, bd["idx"], bd["parent_idx"])
            p.blocks.append(b)
        for bd, b in zip(d["blocks"], p.blocks):
            for name, vd in bd["vars"].items():
                if vd.get("is_parameter"):
                    var = Parameter(b, vd["shape"], vd["dtype"], name=name)
                else:
                    var = Variable(
                        b, name=name, shape=vd["shape"], dtype=vd["dtype"],
                        lod_level=vd.get("lod_level", 0),
                        persistable=vd.get("persistable", False),
                        stop_gradient=vd.get("stop_gradient", False))
                b.vars[name] = var
            for od in bd["ops"]:
                attrs = {}
                for k, v in od["attrs"].items():
                    if isinstance(v, dict) and "__block__" in v:
                        v = p.blocks[v["__block__"]]
                    elif isinstance(v, dict) and "__ndarray__" in v:
                        v = np.asarray(v["__ndarray__"], dtype=v["dtype"])
                    attrs[k] = v
                op = Operator.__new__(Operator)
                op.block = b
                op.type = od["type"]
                op.inputs = {k: list(v) for k, v in od["inputs"].items()}
                op.outputs = {k: list(v) for k, v in od["outputs"].items()}
                # _AttrDict so in-place attr edits on a LOADED program
                # also version-bump the executor's compile-cache key
                op.attrs = _AttrDict(op, attrs)
                b.ops.append(op)
        return p

    def fingerprint(self) -> str:
        """Stable content hash; the compile-cache key component."""
        blob = json.dumps(self.to_dict(), sort_keys=True, default=str)
        return hashlib.sha1(blob.encode()).hexdigest()

    def clone(self, for_test: bool = False) -> "Program":
        """Deep-copy the program.  With ``for_test=True``, flips ops with an
        ``is_test`` attribute (dropout, batch_norm) into inference mode
        (reference: fluid framework.py Program.clone / inference_optimize)."""
        p = copy.deepcopy(self)
        # the content-hash cache must not survive the copy: the clone may
        # differ only in op attrs (is_test), which the cheap op/var-count
        # staleness check cannot see
        p.invalidate_cache()
        if for_test:
            for block in p.blocks:
                for op in block.ops:
                    if "is_test" in _ops_with_is_test(op.type):
                        op.attrs["is_test"] = True
                # Strip training-only ops (reference: fluid clone(for_test)
                # drops backward/optimize-role ops): grad ops, parameter
                # updates, and the LR-scheduler step counter.  Without
                # this a test-program run would keep TRAINING the model.
                block.ops = [op for op in block.ops
                             if not _is_training_only_op(op)]
        return p

    def invalidate_cache(self):
        """Drop the cached fingerprint (call after mutating op attrs
        in place; structural mutations are detected automatically)."""
        if hasattr(self, "_fp_cache"):
            del self._fp_cache

    def prune(self, targets) -> "Program":
        """Dead-op elimination given fetch targets (reference:
        framework/prune.cc, incl. its sub-block recursion at
        prune.cc:133).  Keeps ops whose outputs (transitively) feed a
        target; a kept control-flow op also keeps every variable its
        sub-blocks read from the enclosing scope, even when not named in
        the op's own inputs.  Delegates to the analysis layer's
        fetch-driven backward slicer (analysis/optimize.py), which the
        optimizer's dce pass shares."""
        from paddle_tpu.analysis.optimize import backward_slice

        return backward_slice(self, _as_name_list(targets),
                              keep_side_effects=False)


def _sub_block_external_reads(op) -> set:
    """Variables an op's sub-blocks (Block-valued attrs) read from the
    enclosing scope: union of sub-block op inputs (recursively) minus
    names produced inside the sub-block (reference: prune.cc:133)."""
    reads: set = set()
    for v in op.attrs.values():
        if not isinstance(v, Block):
            continue
        produced: set = set()
        for sub_op in v.ops:
            reads |= set(sub_op.input_arg_names) - produced
            reads |= _sub_block_external_reads(sub_op)
            produced |= set(sub_op.output_arg_names)
    return reads


def _ops_with_is_test(op_type: str):
    return {"dropout": ("is_test",), "batch_norm": ("is_test",)}.get(op_type, ())


# Parameter-update op types (reference: fluid optimizer.py appends these;
# clone(for_test) must drop them so test runs don't train).
_OPTIMIZER_OP_TYPES = frozenset({
    "sgd", "momentum", "adam", "adamax", "adagrad", "decayed_adagrad",
    "adadelta", "rmsprop", "ftrl", "proximal_gd", "proximal_adagrad",
})


def _is_training_only_op(op) -> bool:
    # primary signal: the role stamped by Optimizer._create_optimization_pass
    if op.attrs.get("op_role") == "optimize":
        return True
    # fallbacks for hand-built programs that skip the optimizer classes
    if op.type in _OPTIMIZER_OP_TYPES:
        return True
    if any("@GRAD" in name for name in op.output_arg_names):
        return True
    # LR-scheduler global-step bump (lr_scheduler.py _counter): in-place
    # increment of the persistable step var
    if op.type == "increment" and any(
            "@lr_global_step@" in n for n in op.output_arg_names):
        return True
    return False


# ---------------------------------------------------------------------------
# Default programs + guards (reference: fluid framework.py:875-886)
# ---------------------------------------------------------------------------

_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


def switch_main_program(p: Program) -> Program:
    global _main_program
    old, _main_program = _main_program, p
    return old


def switch_startup_program(p: Program) -> Program:
    global _startup_program
    old, _startup_program = _startup_program, p
    return old


_RECOMPUTE_SEG = [None]
_RECOMPUTE_COUNTER = [0]
_RECOMPUTE_OP_IDX = [0]


@contextlib.contextmanager
def recompute_scope():
    """Mark every op appended inside this scope as one rematerialization
    segment: the executor wraps the segment in ``jax.checkpoint`` so its
    activations are NOT saved for backward — they recompute from the
    segment inputs during the gradient pass, trading MXU FLOPs for HBM
    (the standard TPU memory/compute trade the reference era solved
    with smaller batches).  Random ops inside the segment replay
    deterministically (the segment derives its keys from one captured
    sub-key).  Host-side side effects (print/save ops) inside the scope
    fire again during recompute — keep them outside.

    Usage::

        with fluid.recompute_scope():
            h = fluid.layers.fc(h, 4096, act="relu")
            h = fluid.layers.fc(h, 4096, act="relu")
    """
    _RECOMPUTE_COUNTER[0] += 1
    seg = _RECOMPUTE_COUNTER[0]
    prev = _RECOMPUTE_SEG[0]
    # the segment key op runs OUTSIDE the segment: forward and the
    # backward recompute both derive their randomness from its output,
    # so dropout masks replay identically
    blk = default_main_program().global_block()
    key_name = f"__segkey_{seg}__"
    blk.create_var(name=key_name, shape=(), dtype="int32",
                   stop_gradient=True)
    blk.append_op(type="segment_rng_key", outputs={"Out": [key_name]},
                  attrs={"__seg_id__": seg})
    _RECOMPUTE_SEG[0] = seg
    try:
        yield
    finally:
        _RECOMPUTE_SEG[0] = prev


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


def reset_default_programs():
    """Fresh default programs + name counter (used by tests)."""
    global _main_program, _startup_program, _name_gen
    _main_program = Program()
    _startup_program = Program()
    _name_gen.ids.clear()
