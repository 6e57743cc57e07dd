"""Program/Block/Operator/Variable IR — the fluid graph model, TPU-native.

Reference: paddle/fluid/framework/framework.proto:42-205 (ProgramDesc =
BlockDesc[] of VarDesc[] + OpDesc[]) and python/paddle/fluid/framework.py
(Program:3921, Block:2436, Operator:1839, Variable:928).  Semantics kept:
two-program idiom (startup/main), nested blocks for control flow, named
variadic input/output slots, persistable vars, stop_gradient.  Execution
differs: a Block is not interpreted op-by-op; executor.py lowers it to one
jaxpr and XLA-compiles it (the "kernel" is a lowering rule, not CUDA).
"""
from __future__ import annotations

import contextlib
import itertools
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

_dtype_aliases = {
    "float32": "float32", "fp32": "float32", np.float32: "float32",
    "float64": "float64", "fp64": "float64", np.float64: "float64",
    "float16": "float16", "fp16": "float16", np.float16: "float16",
    "bfloat16": "bfloat16", "bf16": "bfloat16",
    "int64": "int64", np.int64: "int64",
    "int32": "int32", np.int32: "int32",
    "int16": "int16", "int8": "int8", "uint8": "uint8",
    "bool": "bool", bool: "bool",
}


# framework.proto VarType.Type enum values (framework.proto:104) — cast-op
# attrs and saved OpDescs carry these ints, not strings
_PROTO_DTYPE = {0: "bool", 1: "int16", 2: "int32", 3: "int64",
                4: "float16", 5: "float32", 6: "float64", 20: "uint8",
                21: "int8", 22: "bfloat16"}


def convert_dtype(dtype) -> str:
    """Normalise a user dtype spec to a canonical string name."""
    if isinstance(dtype, (int, np.integer)) \
            and not isinstance(dtype, bool) and int(dtype) in _PROTO_DTYPE:
        # numpy ints must hit this branch too: np.int64(5) would otherwise
        # fall through to np.dtype() and silently resolve as 'int64'
        return _PROTO_DTYPE[int(dtype)]
    if isinstance(dtype, str) and dtype in _dtype_aliases:
        return _dtype_aliases[dtype]
    if dtype in _dtype_aliases:
        return _dtype_aliases[dtype]
    try:
        return np.dtype(dtype).name
    except TypeError:
        # jax dtypes like jnp.bfloat16
        name = getattr(dtype, "name", None) or str(dtype)
        if name in _dtype_aliases:
            return _dtype_aliases[name]
        raise ValueError(f"unsupported dtype: {dtype!r}")


_64_TO_32 = {"int64": "int32", "uint64": "uint32", "float64": "float32"}


def device_dtype(dtype) -> str:
    """Canonical dtype name as it will exist ON DEVICE: 64-bit names map
    to their 32-bit counterparts when jax x64 mode is off (an explicit
    choice — requesting the 64-bit dtype would produce the same array
    plus a truncation warning per call).  Op lowerings use this for any
    dtype request that came from program attrs."""
    import jax
    name = convert_dtype(dtype)
    if not jax.config.jax_enable_x64:
        return _64_TO_32.get(name, name)
    return name


_name_counters: Dict[str, itertools.count] = {}


def unique_name(prefix: str = "tmp") -> str:
    """fluid.unique_name analog (python/paddle/fluid/unique_name.py)."""
    c = _name_counters.setdefault(prefix, itertools.count())
    return f"{prefix}_{next(c)}"


def reset_unique_name():
    _name_counters.clear()


class Variable:
    """A named tensor in a Block (VarDesc analog, framework.proto:104-167).

    Shape/dtype here are *advisory* IR metadata — the compiled function gets
    real shapes from the fed arrays; -1 marks a dynamic (batch) dim exactly as
    in fluid.  No LoD: ragged sequences are represented as padded tensors plus
    explicit length/segment-id tensors (SURVEY §5 long-context note).
    """

    def __init__(self, block: "Block", name: str, shape=None, dtype="float32",
                 persistable: bool = False, stop_gradient: bool = False,
                 is_data: bool = False, trainable: bool = True):
        self.block = block
        self.name = name
        self.shape = tuple(int(d) for d in shape) if shape is not None else None
        self.dtype = convert_dtype(dtype) if dtype is not None else None
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.trainable = trainable
        self.op: Optional[Operator] = None   # defining op (set by append_op)

    # --- operator sugar: building graph like fluid Variables do -------------
    def _binary(self, op_type, other, reverse=False):
        from ..fluid import layers
        other = layers.tensor._to_variable(self.block, other, self.dtype)
        x, y = (other, self) if reverse else (self, other)
        return layers.elementwise_op(op_type, x, y)

    def __add__(self, o): return self._binary("elementwise_add", o)
    def __radd__(self, o): return self._binary("elementwise_add", o, True)
    def __sub__(self, o): return self._binary("elementwise_sub", o)
    def __rsub__(self, o): return self._binary("elementwise_sub", o, True)
    def __mul__(self, o): return self._binary("elementwise_mul", o)
    def __rmul__(self, o): return self._binary("elementwise_mul", o, True)
    def __truediv__(self, o): return self._binary("elementwise_div", o)
    def __rtruediv__(self, o): return self._binary("elementwise_div", o, True)
    def __pow__(self, o): return self._binary("elementwise_pow", o)
    def __rpow__(self, o): return self._binary("elementwise_pow", o, True)
    def __floordiv__(self, o): return self._binary("elementwise_floordiv", o)
    def __rfloordiv__(self, o):
        return self._binary("elementwise_floordiv", o, True)
    def __mod__(self, o): return self._binary("elementwise_mod", o)
    def __rmod__(self, o): return self._binary("elementwise_mod", o, True)
    def __neg__(self):
        from ..fluid import layers
        return layers.scale(self, scale=-1.0)
    def __matmul__(self, o):
        from ..fluid import layers
        return layers.matmul(self, o)

    @property
    def ndim(self):
        return len(self.shape) if self.shape is not None else None

    def __repr__(self):
        return (f"Variable(name={self.name!r}, shape={self.shape}, "
                f"dtype={self.dtype}, persistable={self.persistable})")


class Parameter(Variable):
    """Persistable trainable variable (fluid framework.py Parameter)."""

    def __init__(self, block, name, shape, dtype="float32", trainable=True,
                 regularizer=None, need_clip=True, **kw):
        super().__init__(block, name, shape=shape, dtype=dtype,
                         persistable=True, stop_gradient=not trainable,
                         trainable=trainable)
        self.regularizer = regularizer
        self.need_clip = need_clip
        self.is_distributed = False
        # optional sharding annotation: PartitionSpec-like tuple over mesh axes
        self.sharding: Optional[tuple] = None


class Operator:
    """OpDesc analog: type + named input/output var-name lists + attrs."""

    def __init__(self, block: "Block", type: str,
                 inputs: Dict[str, List[str]], outputs: Dict[str, List[str]],
                 attrs: Optional[Dict[str, Any]] = None):
        self.block = block
        self.type = type
        self.inputs = {k: list(v) for k, v in inputs.items()}
        self.outputs = {k: list(v) for k, v in outputs.items()}
        self.attrs = dict(attrs or {})

    def input(self, slot: str) -> List[str]:
        return self.inputs.get(slot, [])

    def output(self, slot: str) -> List[str]:
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self):
        return [n for v in self.inputs.values() for n in v]

    @property
    def output_arg_names(self):
        return [n for v in self.outputs.values() for n in v]

    def attr(self, name: str, default=None):
        return self.attrs.get(name, default)

    def set_attr(self, name: str, val) -> None:
        """Mutate an attr on an op already in the graph, bumping the
        program's mutation version: an in-place rewrite keeps the op count
        AND ``_version`` unchanged, so a bare ``op.attrs[k] = v`` would let
        the executor's ``_fingerprint`` cache serve a stale digest (a
        cached executable compiled for the OLD attr value)."""
        self.attrs[name] = val
        self.block.program._bump_version()

    # reference OpDesc spelling (framework.py Operator._update_desc_attr)
    _update_desc_attr = set_attr

    def __repr__(self):
        return f"Op({self.type}: {self.inputs} -> {self.outputs})"


class Block:
    """BlockDesc analog: ordered ops + named vars, with parent scoping for
    control-flow sub-blocks (framework.proto BlockDesc.parent_idx)."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def parent_block(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    def var(self, name: str) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise KeyError(f"variable '{name}' not found in block {self.idx}")
        return v

    def has_var(self, name: str) -> bool:
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        b = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent_block
        return None

    def create_var(self, name=None, shape=None, dtype="float32",
                   persistable=False, stop_gradient=False, is_data=False,
                   **kw) -> Variable:
        name = name or unique_name()
        v = Variable(self, name, shape=shape, dtype=dtype,
                     persistable=persistable, stop_gradient=stop_gradient,
                     is_data=is_data)
        self.vars[name] = v
        return v

    def create_parameter(self, name, shape, dtype="float32", trainable=True,
                         **kw) -> Parameter:
        p = Parameter(self, name, shape, dtype=dtype, trainable=trainable, **kw)
        # parameters live in block 0 (fluid global block convention)
        self.program.global_block().vars[name] = p
        return p

    def append_op(self, type: str, inputs: Dict[str, Any] = None,
                  outputs: Dict[str, Any] = None,
                  attrs: Dict[str, Any] = None) -> Operator:
        def norm(d):
            out = {}
            for k, v in (d or {}).items():
                if v is None:
                    continue
                if isinstance(v, (Variable, str)):
                    v = [v]
                out[k] = [x.name if isinstance(x, Variable) else x for x in v]
            return out
        op = Operator(self, type, norm(inputs), norm(outputs), attrs)
        if _current_device is not None and "op_device" not in op.attrs:
            # device_guard annotation — consumed by the pipeline splitter
            # (reference: operator.cc:1180 per-op `op_device` for pipeline)
            op.attrs["op_device"] = _current_device
        self.ops.append(op)
        self.program._bump_version()
        for names in op.outputs.values():
            for n in names:
                if self._find_var_recursive(n) is None:
                    self.create_var(name=n)
                var = self._find_var_recursive(n)
                var.op = op
        _infer_op_shapes(self, op)
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None):
        op = self.append_op(type, inputs, outputs, attrs)
        self.ops.insert(0, self.ops.pop())
        return op

    def _remove_op(self, index: int, end: Optional[int] = None):
        """Remove ``ops[index:end]`` (reference Block._remove_op), bumping
        the program mutation version.  Passes that pop-and-reinsert ops
        keep the op count stable, so without the bump the executor's
        ``_fingerprint`` count-based safety net cannot see the change."""
        del self.ops[index:(index + 1) if end is None else end]
        self.program._bump_version()

    def _insert_op(self, index: int, type: str, inputs=None, outputs=None,
                   attrs=None) -> Operator:
        """Build an op (var creation + shape inference, exactly like
        append_op) and place it at ``index`` (reference Block._insert_op).
        The bump rides on append_op."""
        op = self.append_op(type, inputs, outputs, attrs)
        self.ops.insert(index, self.ops.pop())
        return op

    def _insert_op_obj(self, index: int, op: Operator) -> Operator:
        """Insert an already-constructed Operator at ``index`` — the
        pattern-rewriter path, where ops are assembled detached and spliced
        in.  A bare ``ops.insert`` would keep ``_version`` stale exactly
        like the documented ``_remove_op`` hazard."""
        self.ops.insert(index, op)
        for names in op.outputs.values():
            for n in names:
                if self._find_var_recursive(n) is None:
                    self.create_var(name=n)
        self.program._bump_version()
        return op

    def _remove_var(self, name: str) -> bool:
        """Drop a var from this block (reference Block._remove_var),
        bumping the version: serialized descs and pass-managed rewrites
        key off it."""
        existed = self.vars.pop(name, None) is not None
        if existed:
            self.program._bump_version()
        return existed

    def _rename_var(self, old: str, new: str) -> Optional[Variable]:
        """Rename a var and every reference to it (reference
        Block._rename_var): op input/output lists in ALL blocks (sub-block
        ops capture outer vars by name), and the name-carrying control-flow
        attrs (`true_outs`, read by the conditional_block pass-through
        path).  Bumps the version: these name lists feed the executor
        fingerprint."""
        v = self.vars.pop(old, None)
        if v is not None:
            v.name = new
            self.vars[new] = v
        for b in self.program.blocks:
            for op in b.ops:
                for d in (op.inputs, op.outputs):
                    for slot, names in d.items():
                        d[slot] = [new if n == old else n for n in names]
                for k, val in op.attrs.items():
                    if k in ("true_outs", "false_outs") and isinstance(
                            val, (list, tuple)):
                        op.attrs[k] = type(val)(
                            new if n == old else n for n in val)
        self.program._bump_version()
        return v

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.program.global_block().vars.values()
                if isinstance(v, Parameter)]


_DEFAULT_DTYPE = "float32"

_FLOAT_DTYPES = ("float16", "bfloat16", "float32", "float64")


def set_default_dtype(d) -> None:
    """paddle.set_default_dtype analog (reference
    python/paddle/framework/framework.py:20): the dtype layers use for
    parameters created without an explicit dtype."""
    global _DEFAULT_DTYPE
    try:
        name = convert_dtype(d)
    except (TypeError, ValueError):
        name = str(d)
    if name not in _FLOAT_DTYPES:
        raise TypeError(
            f"set_default_dtype only supports {_FLOAT_DTYPES}, got {name!r}")
    _DEFAULT_DTYPE = name


def get_default_dtype() -> str:
    return _DEFAULT_DTYPE


class Program:
    """ProgramDesc analog.  fluid's two-program idiom is kept: layer calls
    append compute ops to the *main* program and parameter-initialisation ops
    to the *startup* program (python/paddle/fluid/framework.py Program)."""

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed: Optional[int] = None
        self._op_seed_counter = 0
        # annotations consumed by the executor / meta-optimizers
        self._amp_enabled = False
        self._amp_dtype = "bfloat16"
        self._hints: Dict[str, Any] = {}
        # executor fingerprint cache: bumped on every op mutation so the
        # per-step SHA-1 recompute is amortised away (executor._fingerprint)
        self._version = 0
        self._fp_cache = None

    def _bump_version(self):
        self._version += 1
        self._fp_cache = None

    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx=None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def next_op_seed(self) -> int:
        base = self.random_seed if self.random_seed is not None else 0
        self._op_seed_counter += 1
        return base * 1_000_003 + self._op_seed_counter

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def all_parameters(self):
        return self.global_block().all_parameters()

    @property
    def desc(self):
        """ProgramDesc protobuf snapshot (reference Program.desc is a live
        C++ wrapper; here the proto is regenerated from the IR on access
        — `program.desc.SerializeToString()` is the `__model__` bytes)."""
        from . import proto_serde
        return proto_serde.program_to_proto(self)

    def to_string(self, throw_on_error=True, with_details=False):
        """Debug string (reference framework.py:4655 Program.to_string):
        the protobuf text format of the ProgramDesc.  With
        throw_on_error=False a serialization failure becomes part of the
        debug output instead of raising (the reference contract)."""
        from google.protobuf import text_format
        try:
            return text_format.MessageToString(self.desc)
        except ValueError:
            if throw_on_error:
                raise
            return f"<Program: not fully serializable " \
                   f"({len(self.blocks)} blocks)>"

    def __str__(self):
        return self.to_string(True, False)

    @staticmethod
    def parse_from_string(binary_str: bytes) -> "Program":
        """Deserialize a Program from ProgramDesc protobuf bytes
        (reference framework.py:4657; parameters come back as plain
        persistable vars — values live in the scope, not the IR)."""
        from . import proto_serde
        return proto_serde.program_from_proto_bytes(binary_str)

    def clone(self, for_test: bool = False) -> "Program":
        """Structural clone; with for_test=True marks inference mode (dropout
        and batch_norm switch to eval behaviour via ctx.is_test), strips the
        backward/optimizer tail, and dead-code-eliminates by reachability —
        ops feeding only the removed tail (lr counters, grad-clip scratch)
        go too (framework/prune.cc semantics, not just the op-role filter)."""
        import copy
        p = copy.deepcopy(self)
        if for_test:
            p._hints["is_test"] = True
            p._hints.pop("recompute_checkpoints", None)
            p._hints.pop("pipeline_microbatches", None)
            # pass 1: strip the backward/optimizer tail from EVERY block
            # first, so the parent-block reachability scan below never sees
            # captures of sub-block grad ops that are about to be deleted
            for b in p.blocks:
                b.ops = [op for op in b.ops
                         if op.attr("op_role", 0) == 0 and
                         not op.type.endswith("_grad") and
                         op.type not in _OPTIMIZER_OP_TYPES]
            # pass 2: leaf-output seed; no state-write keep: eval must not
            # run lr counters or other train-state updates
            for b in p.blocks:
                b.ops = prune_ops(b, b.ops, targets=None,
                                  keep_state_writes=False)
        p._bump_version()
        return p

    def _prune(self, targets) -> "Program":
        """Program pruned to ops that `targets` (vars or names) depend on
        (reference Program._prune -> framework/prune.cc)."""
        import copy
        names = [t.name if isinstance(t, Variable) else str(t)
                 for t in (targets if isinstance(targets, (list, tuple))
                           else [targets])]
        p = copy.deepcopy(self)
        b = p.global_block()
        b.ops = prune_ops(b, b.ops, targets=names, keep_state_writes=False)
        p._bump_version()
        return p

    def __repr__(self):
        n_ops = sum(len(b.ops) for b in self.blocks)
        return f"Program(blocks={len(self.blocks)}, ops={n_ops})"


_BATCH_PLACEHOLDER = 1031   # prime stand-in for -1 dims during eval_shape


def _infer_op_shapes(block: "Block", op: "Operator"):
    """Advisory shape/dtype inference: run the op's own lowering rule under
    jax.eval_shape (abstract — no compute).  This replaces the reference's
    676 per-op C++ InferShape functions (operator.cc:1095) with one
    mechanism; ops that need concrete values simply leave shapes unset."""
    from ..ops.registry import has_op, get_op, LoweringContext
    if not has_op(op.type) or op.type in ("generic_grad", "while",
                                          "conditional_block"):
        return
    import jax
    import jax.numpy as jnp
    opdef = get_op(op.type)
    ins = {}
    had_batch = False
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            v = block._find_var_recursive(n)
            if v is None or v.shape is None or v.dtype is None:
                return
            shape = tuple(_BATCH_PLACEHOLDER if d == -1 else d
                          for d in v.shape)
            had_batch = had_batch or (-1 in v.shape)
            try:
                dt = jnp.dtype(v.dtype)
            except TypeError:
                return
            vals.append(jax.ShapeDtypeStruct(shape, dt))
        ins[slot] = vals
    ctx = LoweringContext()
    try:
        outs = jax.eval_shape(lambda i: opdef.fn(i, op.attrs, ctx), ins)
    except Exception:
        return
    for slot, names in op.outputs.items():
        for name, o in zip(names, outs.get(slot, []) or []):
            var = block._find_var_recursive(name)
            if var is None or o is None:
                continue
            if var.shape is None:
                var.shape = tuple(
                    -1 if (had_batch and d == _BATCH_PLACEHOLDER) else d
                    for d in o.shape)
            if var.dtype is None or var.dtype == "float32":
                var.dtype = str(jnp.dtype(o.dtype))


_OPTIMIZER_OP_TYPES = frozenset({
    "sgd", "momentum", "adam", "adamw", "adagrad", "rmsprop", "lamb",
    "lars_momentum", "ftrl", "dpsgd", "dgc_momentum",
})

# ops kept during pruning regardless of reachability: cross-device and
# control-flow effects the dataflow scan can't see (select_input/output are
# pure dataflow with declared slots — plain reachability covers them)
_SIDE_EFFECT_OP_TYPES = frozenset({
    "send_v2", "partial_send", "barrier", "c_sync_calc_stream",
    "c_sync_comm_stream", "while", "conditional_block", "py_func", "print",
})

_SUB_BLOCK_ATTRS = ("sub_block", "cond_block", "true_block", "false_block")


def _op_reads(block, op, _seen=None):
    """All vars an op may read, INCLUDING captures of its control-flow
    sub-blocks (cond/while bodies read outer vars that are not declared
    as op inputs)."""
    reads = list(op.input_arg_names)
    if (op.type == "conditional_block"
            and op.attrs.get("false_block", -1) < 0):
        # pass-through false path READS the outputs' prior values
        reads += list(op.attrs.get("true_outs", ()))
    _seen = _seen if _seen is not None else set()
    prog = block.program
    for attr in _SUB_BLOCK_ATTRS:
        idx = op.attrs.get(attr)
        if isinstance(idx, int) and 0 <= idx < len(prog.blocks) \
                and idx not in _seen:
            _seen.add(idx)
            sub = prog.blocks[idx]
            written = set()
            for sop in sub.ops:
                reads += [n for n in _op_reads(sub, sop, _seen)
                          if n not in written]
                written.update(sop.output_arg_names)
    return reads


def prune_ops(block, ops, targets=None, keep_state_writes=True,
              extra_state=(), feeds=()):
    """Backward-reachability prune (framework/prune.cc analog).

    Keeps an op iff it (a) produces a var in the needed set, seeded from
    `targets` (None = every NON-persistable leaf output — predictions,
    losses, metrics; persistable leaves are training state whose updates
    are exactly what a for_test clone must drop), (b) writes a persistable
    or `extra_state` var while `keep_state_writes` (optimizer / BN-stats
    updates must survive a fetch-only prune), or (c) has side effects the
    dataflow can't see.  Kept ops contribute their reads — including
    control-flow sub-block captures — to the needed set, one reverse pass.

    `feeds` names vars the caller materialises directly: an op whose
    needed outputs are ALL fed is dropped and its inputs are not
    traversed — feeding an intermediate var runs the program FROM that
    var, exactly the reference's prune-with-input semantics
    (framework/prune.cc feed targets; executor.py feed of any var)."""
    def persistable(n):
        # resolve through parent blocks: sub-block ops write global-block
        # counters (GradientMerge-style state updated inside while bodies)
        v = block._find_var_recursive(n)
        return v is not None and v.persistable

    extra = set(extra_state)
    fed = set(feeds)
    if targets is None:
        consumed = {n for op in ops for n in _op_reads(block, op)}
        needed = {n for op in ops for n in op.output_arg_names
                  if n not in consumed and not persistable(n)}
    else:
        needed = set(targets)
    kept = []
    for op in reversed(ops):
        outs = op.output_arg_names
        state_write = keep_state_writes and any(
            persistable(n) or n in extra for n in outs)
        needed_outs = [n for n in outs if n in needed]
        if (fed and needed_outs and not state_write
                and op.type not in _SIDE_EFFECT_OP_TYPES
                and all(n in fed for n in needed_outs)
                # in-place op on the fed var (reads the same name it
                # writes): the op transforms the fed value — keep it
                and not (set(needed_outs) & set(_op_reads(block, op)))):
            continue          # feed satisfies everything this op is for
        keep = (op.type in _SIDE_EFFECT_OP_TYPES or needed_outs
                or state_write)
        if keep:
            kept.append(op)
            needed.update(_op_reads(block, op))
    kept.reverse()
    return kept

# ---------------------------------------------------------------------------
# device_guard: pipeline stage placement (fluid.device_guard analog —
# python/paddle/fluid/framework.py device_guard; ops appended inside the
# guard carry an `op_device` attr, consumed by PipelineOptimizer's splitter)
# ---------------------------------------------------------------------------
_current_device = None


class device_guard:
    """`with fluid.device_guard("tpu:1"):` — annotate appended ops with a
    pipeline stage device."""

    def __init__(self, device=None):
        self.device = device
        self._prev = None

    def __enter__(self):
        global _current_device
        self._prev = _current_device
        _current_device = self.device
        return self

    def __exit__(self, *a):
        global _current_device
        _current_device = self._prev
        return False

# ---------------------------------------------------------------------------
# default program machinery (program_guard etc.)
# ---------------------------------------------------------------------------
_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    global _main_program, _startup_program
    prev_main, prev_startup = _main_program, _startup_program
    _main_program = main_program
    if startup_program is not None:
        _startup_program = startup_program
    try:
        yield
    finally:
        _main_program, _startup_program = prev_main, prev_startup


_dygraph_tracer_ = None


def in_dygraph_mode() -> bool:
    return _dygraph_tracer_ is not None


def _set_dygraph_tracer(tracer):
    global _dygraph_tracer_
    _dygraph_tracer_ = tracer


def _dygraph_tracer():
    return _dygraph_tracer_


def cuda_places(device_ids=None):
    """Accelerator places (framework.py cuda_places): TPU chips here."""
    from .core import TPUPlace
    import jax
    if device_ids is None:
        try:
            device_ids = range(len(jax.devices()))
        except RuntimeError:
            device_ids = [0]
    return [TPUPlace(int(i)) for i in device_ids]


def cpu_places(device_count=None, count=None):
    """count= kept as the historical keyword of this build's first
    signature; device_count= matches the reference."""
    from .core import CPUPlace
    import os
    n = device_count or count or int(os.environ.get("CPU_NUM", "1"))
    return [CPUPlace() for _ in range(n)]


def cuda_pinned_places(device_count=None):
    from .core import TPUPinnedPlace
    n = device_count or 1
    return [TPUPinnedPlace() for _ in range(n)]


def require_version(min_version, max_version=None):
    """framework.py require_version analog over the build's version."""
    from .. import __version__

    def parse(v):
        return [int(x) for x in str(v).split(".")[:3] if x.isdigit()]
    cur = parse(__version__)
    if parse(min_version) > cur:
        raise Exception(
            f"installed version {__version__} < required {min_version}")
    if max_version is not None and parse(max_version) < cur:
        raise Exception(
            f"installed version {__version__} > allowed {max_version}")


def load_op_library(path):
    from .core import load_op_library as _l
    return _l(path)
