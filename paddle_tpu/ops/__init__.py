"""Op registry: op type -> pure JAX implementation.

Parity: paddle/fluid/operators/* (REGISTER_OPERATOR / REGISTER_OP_*_KERNEL).
The reference implements ~500 C++/CUDA kernels dispatched per-op on a device
stream. Here every op is a small pure-JAX function invoked while the Executor
traces the whole Program under jit, so XLA sees one graph and fuses across op
boundaries (elementwise into matmul/conv epilogues, etc.) — no per-op launch.

An op impl has signature ``fn(ctx) -> {output_slot: array-or-list}``.
"""

import jax
import jax.numpy as jnp

from ..observability import get_recorder
from ..observability.metrics import global_registry

_REGISTRY = {}
# op type -> the input slots AMP leaves in their own type (register's
# amp_keeps)
_AMP_KEEPS = {}

# trace-time dispatch counter (run_op only executes while the Executor
# traces a program, never on the cached per-step hot path)
_OPS_TRACED = global_registry().counter(
    "ops.traced", "op dispatches into the jax trace (trace-time)")

# --- int64 policy (VERDICT r3 #7; MIGRATION.md "Integer dtypes") -------
# Device integers are int32: fluid's int64 ids/labels are accepted at the
# feed boundary (Executor validates they FIT and converts loudly —
# core/executor.py _canon_feed), and every kernel that would emit or
# request int64 emits the canonical device int instead. jax's x64 mode
# stays off — doubling index widths would halve integer throughput and
# buy nothing until vocab/ids exceed 2^31 (at which point the feed
# boundary errors rather than truncates).
DEVICE_INT = jnp.int32

_CANON_DTYPES = {"int64": "int32", "uint64": "uint32", "float64": "float32"}


def canon_dtype(dtype):
    """Canonicalize a user-requested dtype string per the int64 policy
    (silently narrowing the REQUEST is fine — values are validated at
    the feed boundary; jnp would otherwise warn on every trace)."""
    s = str(dtype)
    return _CANON_DTYPES.get(s, s)


class TensorArray(list):
    """The value of a LoDTensorArray var during tracing: a python list of
    arrays with static length. A dedicated type so run_op can tell an
    array VALUE (stored whole under one output name) apart from a
    multi-output list (zipped across output names)."""


def register(*names, amp_keeps=()):
    """Register an op impl under `names`. amp_keeps: input slots that are
    no matmul operand (a bias that is added), which keep their type when
    amp.cast_model_to_bf16 tags the op."""
    def deco(fn):
        for n in names:
            _REGISTRY[n] = fn
            _AMP_KEEPS[n] = frozenset(amp_keeps)
        return fn
    return deco


def get(name):
    if name not in _REGISTRY:
        raise NotImplementedError(
            f"op '{name}' has no TPU implementation registered in paddle_tpu.ops")
    return _REGISTRY[name]


def has(name):
    return name in _REGISTRY


def registered_ops():
    return sorted(_REGISTRY)


class OpContext:
    """Execution context handed to an op impl during program tracing."""

    __slots__ = ("op", "env", "program", "is_test")

    def __init__(self, op, env, program, is_test=False):
        self.op = op
        self.env = env
        self.program = program
        self.is_test = is_test or bool(op.attrs.get("is_test", False))

    # -- inputs -------------------------------------------------------------
    def _maybe_amp(self, slot, v):
        # White-listed ops tagged by amp.cast_model_to_bf16 consume bf16 on
        # the MXU; params/grads stay fp32 outside (master weights).
        amp = self.op.attrs.get("__amp_dtype__")
        if (amp and slot not in _AMP_KEEPS.get(self.op.type, ())
                and hasattr(v, "dtype")
                and str(v.dtype) in ("float32", "float64")):
            import jax.numpy as jnp
            return v.astype(jnp.dtype(amp))
        return v

    def in_list(self, slot):
        return [self._maybe_amp(slot, self.env[n])
                for n in self.op.input(slot)]

    def in_(self, slot, default=None):
        names = self.op.input(slot)
        return self._maybe_amp(slot, self.env[names[0]]) if names else default

    def has_in(self, slot):
        return bool(self.op.input(slot))

    def attr(self, name, default=None):
        return self.op.attrs.get(name, default)

    def out_name(self, slot):
        names = self.op.output(slot)
        return names[0] if names else None

    def out_var(self, slot):
        name = self.out_name(slot)
        return self.op.block._find_var_recursive(name) if name else None

    # -- rng ----------------------------------------------------------------
    def rng(self):
        """Deterministic per-op PRNG key: base key folded with this op's seed."""
        base = self.env["@RNG@"]
        return jax.random.fold_in(base, self.op.attrs.get("op_seed", 0))


def run_op(op, env, program, is_test=False):
    """Execute one op into env (called during jit tracing)."""
    impl = get(op.type)
    ctx = OpContext(op, env, program, is_test)
    _OPS_TRACED.inc()
    rec = get_recorder()
    if rec.enabled:
        # trace capture live: record where TRACE time goes, per op
        with rec.span(f"op:{op.type}", cat="trace"), \
                jax.named_scope(op.type):
            outs = impl(ctx)
    else:
        # named_scope pushes the framework op name into XLA HLO metadata
        # so device traces (XProf/Perfetto) line up with Program ops;
        # trace-time-only cost, nothing on the cached step path
        with jax.named_scope(op.type):
            outs = impl(ctx)
    if outs:
        for slot, vals in outs.items():
            names = op.output(slot)
            if not isinstance(vals, (list, tuple)) or \
                    isinstance(vals, TensorArray):
                vals = [vals]
            for name, val in zip(names, vals):
                env[name] = val


# Populate the registry.
from . import math_ops        # noqa: E402,F401
from . import activation_ops  # noqa: E402,F401
from . import tensor_ops      # noqa: E402,F401
from . import nn_ops          # noqa: E402,F401
from . import loss_ops        # noqa: E402,F401
from . import random_ops      # noqa: E402,F401
from . import optimizer_ops   # noqa: E402,F401
from . import sequence_ops    # noqa: E402,F401
from . import control_flow_ops  # noqa: E402,F401
from . import collective_ops  # noqa: E402,F401
from . import metric_ops      # noqa: E402,F401
from . import detection_ops   # noqa: E402,F401
from . import rnn_ops         # noqa: E402,F401
from . import attention_ops   # noqa: E402,F401
from . import beam_search_ops  # noqa: E402,F401
from . import quant_ops       # noqa: E402,F401
from . import crf_ops         # noqa: E402,F401
from . import ctc_ops         # noqa: E402,F401
from . import sampling_ops    # noqa: E402,F401
from . import rcnn_ops        # noqa: E402,F401
from . import match_ops       # noqa: E402,F401
