"""The compiled path + the elementwise fusion queue.

Two layers of the paper's performance story live here:

1. **The jit bridge** (paper §5.1/§7 "PyTorch JIT" → TorchScript analogue).
   Eager mode pays per-op Python dispatch, exactly as PyTorch does; the
   paper's answer is a JIT that runs the model outside the interpreter.  On
   JAX the natural analogue is ``jax.jit``: because :class:`repro.Tensor`
   is a registered pytree, *unmodified* eager model code can be traced once
   and replayed as a single fused XLA executable.  ``repro.compile(fn)`` is
   therefore the ``torch.jit.trace``/``torch.compile`` of this framework:
   tensor compute is captured, Python control flow is resolved at trace
   time, and retracing happens per input signature (shape/dtype), cached
   thereafter.  Unhashable static arguments fall back to uncached eager
   execution with a warning counter instead of raising.

2. **The elementwise fusion queue** (the §5 small-op fast path).  Inside
   ``with repro.fuse.fusion():`` every elementwise op (add, mul, exp,
   relu, ...) returns a *pending* tensor recording (op, statics, parents)
   instead of dispatching.  At a materialization point — ``.numpy()``,
   ``.item()``, a reduction or matmul consuming the chain, ``backward()``,
   any in-place mutation, or a jit boundary — the maximal pending subgraph
   is lowered through the dispatch cache as ONE jitted (or Pallas, on TPU)
   kernel: N Python dispatches become one executable replay.  Semantics
   are preserved exactly:

   * parent values are snapshotted at enqueue (jax arrays are immutable,
     so holding the reference *is* the snapshot), and every in-place
     mutation flushes all pending chains first, so a fused chain always
     computes what eager execution would have computed;
   * autograd records one tape node per flushed chain whose VJP replays a
     cached jitted backward against the chain's external inputs — version
     counters are captured at enqueue time, so mutate-after-use is
     detected exactly as in the per-op tape.
"""

from __future__ import annotations

import functools
import os
import threading
import warnings
import weakref
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import dispatch as _dispatch
from . import stream as _stream
from .autograd import Node, VersionCounter, is_grad_enabled
from .tensor import Storage, Tensor, _is_inexact, _is_tracer, _nbytes_of


# ----------------------------------------------------------------------
# the jit bridge (repro.compile)
# ----------------------------------------------------------------------

def compile(fn: Optional[Callable] = None, *, static_argnums=(),
            donate_argnums=(), seed_cache: bool = False,
            **jit_kwargs) -> Callable:
    """Trace-and-fuse an eager function (models, train steps, ...).

    Works on any function whose tensor arguments are ``repro.Tensor`` /
    pytrees thereof.  Inside the trace the autograd tape is automatically
    disabled (operands are tracers); use :func:`value_and_grad` to compile
    a differentiated step.

    ``seed_cache=True`` makes the compile dispatch-cache-aware: while the
    function is being traced, every op dispatched with a ``static=``
    descriptor *seeds* an eager dispatch-cache entry from its traced
    signature (see ``dispatch.seeding``).  Tracing a model once then
    leaves its eager ``F.*`` surface warm — and the seeded op names are
    exposed on ``wrapper.seeded_ops`` with per-op hit rates available via
    ``repro.dispatch_cache_stats()["per_op"]``.

    If a call hits jax's non-hashable-static-argument error the wrapper
    falls back to running ``fn`` eagerly (uncached) and bumps the dispatch
    cache's ``num_fallback_unhashable`` counter instead of raising.
    """

    def wrap(f: Callable) -> Callable:
        jitted = jax.jit(f, static_argnums=static_argnums,
                         donate_argnums=donate_argnums, **jit_kwargs)
        warned = []
        seeded_ops: list = []

        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            try:
                if seed_cache:
                    # the flag is thread-local and only consulted when f
                    # is actually (re)traced; warm replays never enter
                    # Python, so keeping it armed per call is free
                    with _dispatch.seeding(sink=seeded_ops):
                        return jitted(*args, **kwargs)
                return jitted(*args, **kwargs)
            except (TypeError, ValueError) as e:
                if "hashable" not in str(e):
                    raise
                _dispatch.dispatch_cache().record_fallback("__compile__")
                if not warned:
                    warned.append(True)
                    warnings.warn(
                        f"repro.compile({f.__name__}): non-hashable "
                        f"static argument; running uncompiled "
                        f"(cached counter: num_fallback_unhashable)")
                return f(*args, **kwargs)

        wrapper._jitted = jitted  # expose for .lower()/.compile() tooling
        wrapper.seeded_ops = seeded_ops  # op names seeded at trace time
        return wrapper

    if fn is not None:
        return wrap(fn)
    return wrap


def value_and_grad(fn: Callable, argnums=0, has_aux: bool = False) -> Callable:
    """Functional gradient of an eager-style function, for the compiled
    path.  Differentiation happens in XLA (JAX AD), not on the tape —
    mirroring how TorchScript code is differentiated by its own engine.
    """
    def scalar_fn(*args, **kwargs):
        out = fn(*args, **kwargs)
        if has_aux:
            out, aux = out
            return (out.data if isinstance(out, Tensor) else out), aux
        return out.data if isinstance(out, Tensor) else out

    vg = jax.value_and_grad(scalar_fn, argnums=argnums, has_aux=has_aux)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return vg(*args, **kwargs)

    return wrapper


def grad(fn: Callable, argnums=0, has_aux: bool = False) -> Callable:
    def scalar_fn(*args, **kwargs):
        out = fn(*args, **kwargs)
        if has_aux:
            out, aux = out
            return (out.data if isinstance(out, Tensor) else out), aux
        return out.data if isinstance(out, Tensor) else out

    g = jax.grad(scalar_fn, argnums=argnums, has_aux=has_aux)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return g(*args, **kwargs)

    return wrapper


def block_until_ready(tree: Any) -> Any:
    """Join on async-dispatched work for a pytree of Tensors/arrays."""
    def _block(x):
        if isinstance(x, Tensor):
            x.data.block_until_ready()
        elif isinstance(x, jax.Array):
            x.block_until_ready()
        return x

    return jax.tree_util.tree_map(
        _block, tree, is_leaf=lambda x: isinstance(x, Tensor))


# ----------------------------------------------------------------------
# elementwise fusion queue
# ----------------------------------------------------------------------

# Ops that are safe to defer and fuse: one output, elementwise (or
# pure dtype-cast), no data-dependent shapes.  The second group is the
# nn.functional activation surface — with their ``static=`` descriptors
# in place they fuse across module boundaries (an MLP's
# linear->act->linear chain defers the activations, not just raw-tensor
# arithmetic).  softmax/log_softmax stay out: they reduce over an axis.
ELEMENTWISE_OPS = frozenset({
    "add", "sub", "mul", "div", "pow", "mod", "neg", "abs", "clone",
    "astype", "exp", "log", "sqrt", "rsqrt", "sin", "cos", "tanh",
    "sigmoid", "relu", "erf", "clamp", "maximum", "minimum", "where",
    "masked_fill",
    "relu6", "gelu", "silu", "softplus", "hardswish", "leaky_relu",
    "elu", "dropout",
})

# Chains deeper than this flush eagerly — bounds pending-graph size and
# XLA program length.
MAX_CHAIN_DEPTH = 32

_tls = threading.local()
_FUSION_DEFAULT = os.environ.get("REPRO_FUSION", "0") == "1"


def fusion_enabled() -> bool:
    return getattr(_tls, "fusion_on", _FUSION_DEFAULT)


def set_fusion(flag: bool) -> bool:
    """Enable/disable the fusion queue for this thread; returns the
    previous setting.  Disabling flushes outstanding chains."""
    prev = fusion_enabled()
    if not flag:
        flush_all()
    _tls.fusion_on = bool(flag)
    return prev


class fusion:
    """Context manager: batch elementwise chains into fused kernels.

    >>> with repro.fuse.fusion():
    ...     y = (x * 2 + 1).tanh().exp()   # zero dispatches so far
    ... loss = y.sum()                      # one fused kernel + one sum
    """

    def __init__(self, enabled: bool = True):
        self._enabled = enabled

    def __enter__(self):
        self._prev = fusion_enabled()
        _tls.fusion_on = self._enabled
        return self

    def __exit__(self, *exc):
        flush_all()
        _tls.fusion_on = self._prev


class PendingOp:
    """One deferred elementwise op in a fusion chain."""

    __slots__ = ("name", "fn", "static", "parents", "parent_snap",
                 "shape", "dtype", "needs_grad", "depth")

    def __init__(self, name, fn, static, parents, parent_snap, shape,
                 dtype, needs_grad, depth):
        self.name = name
        self.fn = fn
        self.static = static
        self.parents = parents          # tuple[Tensor]
        self.parent_snap = parent_snap  # jax.Array | None (None: pending)
        self.shape = shape              # inferred output shape
        self.dtype = dtype              # inferred output dtype
        self.needs_grad = needs_grad
        self.depth = depth


def _registry() -> List:
    reg = getattr(_tls, "pending_reg", None)
    if reg is None:
        reg = _tls.pending_reg = []
    return reg


_aval_cache = {}


def _out_aval(name, static, fn, parent_sigs):
    """(shape, dtype) of the op's output, via cached ``jax.eval_shape``.
    ``parent_sigs`` are plain (shape, dtype) tuples — constructing
    ShapeDtypeStructs only on cache miss keeps enqueue cheap."""
    key = (name, static, parent_sigs)
    out = _aval_cache.get(key)
    if out is None:
        aval = jax.eval_shape(
            fn, *[jax.ShapeDtypeStruct(s, d) for (s, d) in parent_sigs])
        out = (tuple(aval.shape), aval.dtype)
        _aval_cache[key] = out
    return out


def try_enqueue(name: str, fn: Callable, static, tensors) -> Optional[Tensor]:
    """Defer an elementwise op, returning its pending output tensor —
    or ``None`` when the op must dispatch immediately (fusion off,
    non-elementwise, tracer operands)."""
    if not fusion_enabled() or name not in ELEMENTWISE_OPS:
        return None
    for t in tensors:
        if t._pending is None and _is_tracer(t._d):
            return None  # inside a jit trace: lower straight to XLA

    parent_sigs = tuple((t.shape, t.dtype) for t in tensors)
    try:
        out_shape, out_dtype = _out_aval(name, static, fn, parent_sigs)
    except Exception:
        return None  # shape inference failed: let the eager path report

    needs_grad = is_grad_enabled() and any(
        (t.requires_grad or t.grad_fn is not None
         or (t._pending is not None and t._pending.needs_grad))
        and _is_inexact(t.dtype)
        for t in tensors)
    # never fuse across a grad-mode boundary: a chain built under
    # no_grad must stay constant (no shared node), and a grad chain must
    # not differentiate through a constant subchain — flush mismatched
    # pending parents so they join as materialized ext inputs
    for t in tensors:
        if t._pending is not None and t._pending.needs_grad != needs_grad:
            flush_tensor(t)
    depth = 1 + max(
        (t._pending.depth for t in tensors if t._pending is not None),
        default=0)
    pend = PendingOp(
        name, fn, static,
        parents=tuple(tensors),
        parent_snap=tuple(
            None if t._pending is not None else t._d for t in tensors),
        shape=out_shape,
        dtype=out_dtype,
        needs_grad=needs_grad,
        depth=depth,
    )

    out = Tensor.__new__(Tensor)
    out._d = None
    out._pending = pend
    out.requires_grad = False
    out.grad = None
    out.grad_fn = None
    out._output_index = 0
    out._version = VersionCounter()
    out._base = None
    out._view_index = None
    out._storage = None

    reg = _registry()
    reg.append(weakref.ref(out))
    if len(reg) > 4096:  # compact dead/flushed refs
        _tls.pending_reg = [r for r in reg
                            if (x := r()) is not None
                            and x._pending is not None]

    if depth >= MAX_CHAIN_DEPTH:
        flush_tensor(out)
    return out


def flush_all() -> None:
    """Materialize every pending chain in this thread (mutation barrier,
    explicit sync point).  Newest-first: flushing a chain's terminal
    materializes its whole subgraph in one fused kernel, so earlier
    registry entries are usually already done by the time we reach them."""
    reg = getattr(_tls, "pending_reg", None)
    if not reg:
        return
    for ref in reversed(list(reg)):
        t = ref()
        if t is not None and t._pending is not None:
            flush_tensor(t)
    reg.clear()


def _can_use_pallas(ext_data, shape) -> bool:
    if jax.default_backend() != "tpu":
        return False
    return (len(shape) >= 1
            and all(tuple(d.shape) == shape for d in ext_data))


def flush_tensor(t: Tensor) -> None:
    """Lower the maximal pending subgraph feeding ``t`` as ONE fused
    multi-output kernel (via the dispatch cache), execute it, and attach
    a single shared tape node.

    Every pending tensor in the subgraph — intermediates included — is
    materialized from the same kernel: tensor ``i`` becomes output ``i``
    of the fused node (the engine's multi-output cotangent accounting
    handles partial consumption, zero-filling unused outputs)."""
    pend = t._pending
    if pend is None:
        return

    steps = []          # (fn, arg_slots, name, static)
    by_slot: List[Tensor] = []  # tmp index -> its pending tensor
    slot_of = {}        # id(pending tensor) -> tmp index
    ext_tensors: List[Tensor] = []
    ext_data: List = []
    ext_ids = {}
    version_records = {}  # ext index -> (counter, value)

    def ext_slot(p: Tensor, snap) -> Tuple[str, int]:
        idx = ext_ids.get(id(p))
        if idx is None:
            idx = len(ext_tensors)
            ext_ids[id(p)] = idx
            ext_tensors.append(p)
            # enqueue-time snapshot; a parent that was pending at enqueue
            # but flushed since uses its materialized value (mutation
            # cannot have intervened: mutation flushes all chains first,
            # which also makes flush-time version records equal to
            # enqueue-time ones)
            ext_data.append(snap if snap is not None else p._d)
            version_records[idx] = (p._version, p._version.value)
        return ("e", idx)

    def visit(x: Tensor) -> int:
        if id(x) in slot_of:
            return slot_of[id(x)]
        p = x._pending
        slots = []
        for parent, snap in zip(p.parents, p.parent_snap):
            if parent._pending is not None:
                slots.append(("t", visit(parent)))
            else:
                slots.append(ext_slot(parent, snap))
        idx = len(steps)
        steps.append((p.fn, tuple(slots), p.name, p.static))
        by_slot.append(x)
        slot_of[id(x)] = idx
        return idx

    visit(t)

    descriptor = tuple((name, static, slots)
                       for (_, slots, name, static) in steps)
    run_steps = [(fn, slots) for (fn, slots, _, _) in steps]

    def fused_fn(*ext):
        tmp = []
        for fn, slots in run_steps:
            args = [ext[i] if kind == "e" else tmp[i]
                    for (kind, i) in slots]
            tmp.append(fn(*args))
        return tuple(tmp)

    diffable = [i for i, d in enumerate(ext_data)
                if _is_inexact(d.dtype)]
    # any step needing grad means the shared node must exist (grad-mode
    # boundaries inside a chain are prevented at enqueue time)
    needs_grad = any(x._pending.needs_grad for x in by_slot)

    # a chain lowered to the Pallas kernel counts under its own op name
    # in dispatch_cache_stats()["per_op"], so a chip run can show that
    # fusion ran natively
    wrap, op_name = None, "__fused__"
    if (_can_use_pallas(ext_data, pend.shape)
            and all(x._pending.shape == pend.shape for x in by_slot)):
        from ..kernels.ops import make_fused_elementwise
        wrap, op_name = make_fused_elementwise, "__fused_pallas__"

    key = _dispatch.make_key(op_name, descriptor, ext_data,
                             bool(needs_grad))
    if key is not None and _dispatch.is_enabled():
        entry = _dispatch.dispatch_cache().get_or_create(
            key, fused_fn, diffable, len(ext_data), wrap=wrap)
        out_data = entry.fwd(*ext_data)
    else:
        entry = None
        if key is None:
            _dispatch.dispatch_cache().record_fallback("__fused__")
        out_data = fused_fn(*ext_data)

    node = None
    if needs_grad:
        # the engine hands a bare cotangent for single-output nodes but
        # fused_fn always returns a tuple — normalize
        def _norm(cot):
            return cot if isinstance(cot, tuple) else (cot,)

        if entry is not None:
            bwd = entry.bwd()
            saved = tuple(ext_data)
            vjp_fn = lambda cot: bwd(saved, _norm(cot))  # noqa: E731
        else:
            _, raw_vjp = _dispatch.partial_vjp(fused_fn, ext_data,
                                               diffable)
            vjp_fn = lambda cot: raw_vjp(_norm(cot))  # noqa: E731
        inputs = [ext_tensors[i] for i in diffable]
        chain = "+".join(name for (_, _, name, _) in steps)
        node = Node(f"fused[{chain}]", vjp_fn, inputs,
                    num_outputs=len(steps))
        node.metadata["out_avals"] = [
            (x._pending.shape, x._pending.dtype) for x in by_slot]
        for i in diffable:
            node.saved_versions.append(version_records[i])

    stream = _stream.current_stream()
    tracing = _is_tracer(out_data[0])
    for idx, x in enumerate(by_slot):
        x._d = out_data[idx]
        x._pending = None
        x.grad_fn = node
        x._output_index = idx
        if not tracing:
            x._storage = Storage(_nbytes_of(out_data[idx]),
                                 stream.stream_id)
    if not tracing:
        stream.enqueue(*out_data)
