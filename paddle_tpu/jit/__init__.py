"""``paddle.jit`` — whole-graph compilation.

Reference: ``python/paddle/jit/`` dy2static (SURVEY.md §2.1, §3.5): AST
rewriting → ProgramDesc → InterpreterCore (+ CINN). TPU-native: the traced
function becomes ONE ``jax.vjp``-differentiable pure program compiled by XLA
— jit *is* the CINN-equivalent graph compiler, and the eager tape splices the
compiled program in as a single GradNode so ``.backward()`` still works.

``jit.save``/``jit.load`` export via ``jax.export`` (StableHLO) — the
``.pdmodel`` analog — falling back to weights-only when export is
unavailable.
"""

from __future__ import annotations

import functools
import os
import pickle
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd
from ..core.tensor import Tensor, to_tensor
from ..enforce import InvalidArgumentError
from ..nn.layer.layers import Layer
from ..observability import flight as _flight
from ..observability import metrics as _obs_metrics
from ..ops.dispatch import run_op
from ..static import InputSpec

__all__ = ["to_static", "enable_to_static", "TracedProgram", "save", "load",
           "ignore_module", "not_to_static", "is_tracing",
           "fused_train_step", "FusedTrainStep", "TranslatedLayer",
           "set_code_level", "set_verbosity", "enable_persistent_cache",
           "persistent_cache_dir"]

_TRACING = [False]

# ---------------------------------------------------------------------------
# Persistent compilation cache (r15 — ROADMAP item 5's knob): opt in to
# JAX's on-disk XLA executable cache so fleet replicas and process
# restarts pay each program's compile cost once per BINARY instead of
# once per process. Whoever deploys the program places the cache:
# ``JAX_COMPILATION_CACHE_DIR`` names the directory when it is set, and
# otherwise it is one fixed path inside the checkout. The path is part of
# the cache's key, so it is never a temporary, per-process or per-run
# name, and nothing else in the tree sets a directory.
# ---------------------------------------------------------------------------

_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_PERSISTENT_CACHE_DIR: List[Optional[str]] = [None]


def enable_persistent_cache(min_compile_time_s: float = 0.0) -> str:
    """Route XLA compiles through JAX's persistent on-disk cache.

    The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set and
    ``<checkout>/.jax_cache`` otherwise. Entries below
    ``min_compile_time_s`` are skipped (0 caches everything — right for
    serving binaries whose whole point is the 2.5 s segment compile
    class). Returns the directory. Safe to call before or after backend
    init."""
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or _DEFAULT_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_s))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches the no-cache decision at the first compile; a reset
    # lets a long-running process opt in mid-flight (the serving
    # engine's build path does exactly this)
    from jax.experimental.compilation_cache import compilation_cache as _cc

    _cc.reset_cache()
    _PERSISTENT_CACHE_DIR[0] = cache_dir
    _flight.record("persistent_cache", dir=cache_dir,
                   min_compile_time_s=float(min_compile_time_s))
    return cache_dir


def persistent_cache_dir() -> Optional[str]:
    """The active persistent-cache directory (None = not enabled)."""
    return _PERSISTENT_CACHE_DIR[0]

# ---------------------------------------------------------------------------
# Compiled-program cache registry (analysis.recompile introspection):
# every object that owns a jit cache (TracedProgram, FusedTrainStep,
# ServingEngine, Optimizer) registers itself here so the recompile-hazard
# lint can enumerate live caches and inspect their keys. Weak refs — the
# registry must not pin models/engines alive.
# ---------------------------------------------------------------------------

_PROGRAM_CACHES: "weakref.WeakSet" = weakref.WeakSet()


def register_compiled_cache(obj) -> None:
    """Register an object exposing ``cache_info() -> {"name", "keys"}``."""
    _PROGRAM_CACHES.add(obj)


def live_program_caches() -> List[Any]:
    return list(_PROGRAM_CACHES)


def is_tracing() -> bool:
    """True while a TracedProgram is being traced (layers use this to skip
    host-side buffer mutation that would leak tracers, e.g. BN running
    stats). Under ``fused_train_step`` a buffer-write COLLECTOR is active
    instead: ``record_buffer_write`` routes new buffer values out of the
    compiled program so running stats keep updating (to_static'd inference
    keeps the documented skip-divergence)."""
    return _TRACING[0]


_BUFFER_COLLECTOR: List[Any] = []  # stack of active write-collectors


def record_buffer_write(tensor, new_value) -> bool:
    """Register a traced buffer update (BN running stats etc.). Returns
    True when a collector consumed it; False → caller should skip."""
    if not _BUFFER_COLLECTOR:
        return False
    _BUFFER_COLLECTOR[-1].append((tensor, new_value))
    return True


def _collect_state(obj) -> Tuple[List[Tensor], List[Tensor], Optional[Layer]]:
    """All parameters (diff) and buffers (non-diff) reachable from fn/layer."""
    params: List[Tensor] = []
    buffers: List[Tensor] = []
    layer: Optional[Layer] = None
    if isinstance(obj, Layer):
        layer = obj
        params = [p for p in obj.parameters() if not p.stop_gradient]
        buffers = obj.buffers()
    elif hasattr(obj, "__self__") and isinstance(obj.__self__, Layer):
        layer = obj.__self__
        params = [p for p in obj.__self__.parameters() if not p.stop_gradient]
        buffers = obj.__self__.buffers()
    else:
        # free variables (nested fn) AND referenced globals (module-level fn
        # using a module-level model) — both are how users close over Layers
        candidates = []
        if hasattr(obj, "__closure__") and obj.__closure__:
            for cell in obj.__closure__:
                try:
                    candidates.append(cell.cell_contents)
                except ValueError:
                    pass
        code = getattr(obj, "__code__", None)
        glb = getattr(obj, "__globals__", None)
        if code is not None and glb is not None:
            for name in code.co_names:
                v = glb.get(name)
                if isinstance(v, Layer):
                    candidates.append(v)
        seen = set()
        for v in candidates:
            if isinstance(v, Layer):
                for p in v.parameters():
                    if not p.stop_gradient and id(p) not in seen:
                        seen.add(id(p))
                        params.append(p)
                for b in v.buffers():
                    if id(b) not in seen:
                        seen.add(id(b))
                        buffers.append(b)
                if layer is None:
                    layer = v
    return params, buffers, layer


class _SwapValues:
    """Temporarily rebind framework tensors to traced jax values."""

    def __init__(self, tensors: Sequence[Tensor], values):
        self.tensors = tensors
        self.values = values

    def __enter__(self):
        self.saved = [t._value for t in self.tensors]
        for t, v in zip(self.tensors, self.values):
            t._value = v

    def __exit__(self, *exc):
        for t, s in zip(self.tensors, self.saved):
            t._value = s
        return False


class TracedProgram:
    """A ``StaticFunction``-analog: call-compatible wrapper that runs the
    python function as one compiled XLA program."""

    def __init__(self, function: Callable, input_spec=None, build_strategy=None,
                 full_graph=True):
        self._orig_fn = function  # state discovery (closure/Layer walking)
        if full_graph:
            # dy2static: rewrite tensor-dependent if/while into lax.cond /
            # lax.while_loop BEFORE tracing (reference ProgramTranslator)
            from .dy2static import convert_to_static

            function = convert_to_static(function)
        self._fn = function
        self._input_spec = input_spec
        self._cache: Dict[Any, Any] = {}  # structure key -> jitted pure fn
        functools.update_wrapper(self, self._orig_fn,
                                 assigned=("__name__", "__doc__", "__qualname__"),
                                 updated=())
        register_compiled_cache(self)

    def cache_info(self) -> Dict[str, Any]:
        """Cache-key introspection for the recompile-hazard lint: each
        key is ``(arg_tree, shape-signature, kwargs, training)`` — many
        shape variants under one structure means an unbucketed dim."""
        return {"name": f"to_static:{getattr(self, '__name__', 'fn')}",
                "keys": list(self._cache.keys())}

    def _make_pure(self, params, buffers, tensor_args, rest_args, rest_kwargs,
                   arg_tree):
        fn = self._fn
        out_store = {}

        def pure(*flat):
            from ..framework import random as _random

            # flat = (rng_key_data, *params, *buffers, *tensor_args): the key
            # is a per-call input so dropout/random ops inside the compiled
            # program get fresh randomness each call instead of a baked mask.
            key_data = flat[0]
            flat = flat[1:]
            n_p, n_b = len(params), len(buffers)
            pvals = flat[:n_p]
            bvals = flat[n_p : n_p + n_b]
            ivals = flat[n_p + n_b :]
            with _SwapValues(list(params) + list(buffers), list(pvals) + list(bvals)):
                args, kwargs = _rebuild_args(arg_tree, ivals, rest_args, rest_kwargs)
                _TRACING[0] = True
                _random.push_trace_key(jax.random.wrap_key_data(key_data))
                try:
                    with autograd.no_grad():
                        out = fn(*args, **kwargs)
                finally:
                    _random.pop_trace_key()
                    _TRACING[0] = False
            flat_out, tree = _flatten_out(out)
            out_store["tree"] = tree
            return tuple(o._value if isinstance(o, Tensor) else o for o in flat_out)

        return pure, out_store

    def __call__(self, *args, **kwargs):
        from ..framework.random import next_key

        if not _to_static_enabled:  # jit.enable_to_static(False): run eager
            return self._orig_fn(*args, **kwargs)
        params, buffers, layer = _collect_state(self._orig_fn)
        tensor_args, arg_tree, rest_args, rest_kwargs = _split_args(args, kwargs)
        pure, out_store = self._make_pure(params, buffers, tensor_args,
                                          rest_args, rest_kwargs, arg_tree)
        rng_input = Tensor(jax.random.key_data(next_key()), stop_gradient=True)
        all_inputs = [rng_input] + list(params) + list(buffers) + list(tensor_args)
        # whole-graph compile: the pure program goes through jax.jit so XLA
        # fuses it end-to-end; jax.vjp over the jitted fn gives the compiled
        # backward, and run_op splices both into the eager tape as ONE node.
        key = (
            _tree_key(arg_tree),
            tuple((tuple(t.shape), str(t.dtype)) for t in all_inputs),
            tuple(sorted(rest_kwargs)) if rest_kwargs else (),
            getattr(layer, "training", None),  # train/eval compile separately
        )
        hit = self._cache.get(key)
        if hit is None:
            jitted = jax.jit(pure)
            self._cache[key] = (jitted, out_store)
            # telemetry: a cache miss on a warm workload is the recompile
            # hazard class (analysis.recompile); the flight event names
            # the program so the postmortem doesn't need the lint rerun
            _obs_metrics.counter("jit.program_cache_misses").inc()
            _flight.record("program_cache_miss",
                           program=f"to_static:"
                                   f"{getattr(self, '__name__', 'fn')}",
                           entries=len(self._cache))
        else:
            jitted, out_store = hit
        out = run_op(getattr(self._fn, "__name__", "traced_program"), jitted, *all_inputs)
        outs = out if isinstance(out, tuple) else (out,)
        tree = out_store["tree"]
        return _unflatten_out(tree, list(outs))

    # introspection
    @property
    def forward(self):
        return self


def _tree_key(tree):
    def k(node):
        kind, payload = node
        if kind == "T":
            return ("T",)
        if kind in ("L", "U"):
            return (kind, tuple(k(v) for v in payload))
        return ("S", repr(payload))

    return tuple(k(n) for n in tree)


def _split_args(args, kwargs):
    """Separate Tensor leaves (traced) from static args."""
    tensor_args: List[Tensor] = []
    tree: List[Any] = []

    def scan(x):
        if isinstance(x, Tensor):
            tensor_args.append(x)
            return ("T", len(tensor_args) - 1)
        if isinstance(x, (list, tuple)):
            return ("L" if isinstance(x, list) else "U", [scan(v) for v in x])
        return ("S", x)

    arg_tree = [scan(a) for a in args]
    return tensor_args, arg_tree, args, kwargs


def _rebuild_args(arg_tree, ivals, rest_args, rest_kwargs):
    def build(node):
        kind, payload = node
        if kind == "T":
            return Tensor(ivals[payload], stop_gradient=True)
        if kind in ("L", "U"):
            seq = [build(v) for v in payload]
            return seq if kind == "L" else tuple(seq)
        return payload

    args = [build(n) for n in arg_tree]
    return args, rest_kwargs


def _flatten_out(out):
    flat: List[Any] = []

    def scan(x):
        if isinstance(x, Tensor):
            flat.append(x)
            return ("T", len(flat) - 1)
        if isinstance(x, (list, tuple)):
            return ("L" if isinstance(x, list) else "U", [scan(v) for v in x])
        if isinstance(x, dict):
            return ("D", {k: scan(v) for k, v in x.items()})
        return ("S", x)

    tree = scan(out)
    return flat, tree


def _unflatten_out(tree, tensors):
    def build(node):
        kind, payload = node
        if kind == "T":
            return tensors[payload]
        if kind in ("L", "U"):
            seq = [build(v) for v in payload]
            return seq if kind == "L" else tuple(seq)
        if kind == "D":
            return {k: build(v) for k, v in payload.items()}
        return payload

    return build(tree)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True):
    """Decorator/wrapper compiling a function or Layer with XLA."""

    def wrap(fn):
        if isinstance(fn, Layer):
            # dy2static the LAYER'S forward (not Layer.__call__, which is
            # framework plumbing) and trace through the normal call path;
            # TracedProgram gets full_graph=False so it won't re-transform
            # Layer.__call__ itself
            orig_fwd = type(fn).forward
            if full_graph:
                from .dy2static import convert_to_static

                conv = convert_to_static(orig_fwd)
                if conv is not orig_fwd:
                    object.__setattr__(fn, "forward",
                                       conv.__get__(fn, type(fn)))
            traced = TracedProgram(fn.__call__, input_spec, full_graph=False)
            return _TracedLayerProxy(fn, traced, orig_forward=orig_fwd)
        return TracedProgram(fn, input_spec, full_graph=full_graph)

    if function is not None:
        return wrap(function)
    return wrap


class _TracedLayerProxy:
    """Layer-like proxy whose __call__ runs the compiled program."""

    def __init__(self, layer: Layer, traced: TracedProgram,
                 orig_forward=None):
        self._layer = layer
        self._traced = traced
        self._orig_forward = orig_forward

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled and self._orig_forward is not None:
            # enable_to_static(False): run the ORIGINAL dygraph forward
            # (to_static replaced it with the dy2static-converted one)
            cur = self._layer.forward
            object.__setattr__(
                self._layer, "forward",
                self._orig_forward.__get__(self._layer, type(self._layer)))
            try:
                return self._layer(*args, **kwargs)
            finally:
                object.__setattr__(self._layer, "forward", cur)
        return self._traced(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._layer, name)


def save(layer, path, input_spec=None, **configs):
    """Export params (+StableHLO program when input_spec given) — the
    ``.pdmodel``/``.pdiparams`` analog."""
    target = layer._layer if isinstance(layer, _TracedLayerProxy) else layer
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    from ..framework.io import save as fsave

    fsave(target.state_dict(), path + ".pdiparams")
    meta = {"class": type(target).__name__}
    if input_spec:
        try:
            from jax import export as jexport

            params = [p for p in target.parameters() if not p.stop_gradient]
            buffers = target.buffers()
            sd = target.state_dict()
            by_id = {id(v): k for k, v in sd.items()}
            meta["param_keys"] = [by_id[id(p)] for p in params]
            meta["buffer_keys"] = [by_id[id(b)] for b in buffers if id(b) in by_id]

            def pure(pvals, bvals, *ivals):
                with _SwapValues(list(params) + list(buffers), list(pvals) + list(bvals)):
                    with autograd.no_grad():
                        out = target(*[Tensor(v, stop_gradient=True) for v in ivals])
                outs = out if isinstance(out, (list, tuple)) else [out]
                return tuple(o._value for o in outs)

            # InputSpec dims of None/-1 (dynamic batch etc.) become
            # jax.export symbolic dimensions in ONE shared scope. A None at
            # axis j is named dyn{j} for specs sharing an (ndim, dtype)
            # signature — the common co-varying case ((x, labels) float
            # pairs, a+b operands) unifies so export succeeds. Specs with
            # distinct signatures get per-spec names dyn{i}_{j} so e.g. an
            # int token stream and a float feature batch are NOT silently
            # equated (ADVICE r1). For explicit control, put a STRING in
            # the InputSpec shape (e.g. ["qlen", 16] vs ["klen", 16]):
            # equal strings unify, distinct ones don't.
            sigs = [(len(s.shape), str(s.dtype)) for s in input_spec]
            scope = None
            specs = []
            for i, s in enumerate(input_spec):
                dims = tuple(s.shape)
                if any(not isinstance(d, int) or d == -1 for d in dims):
                    if scope is None:
                        scope = jexport.SymbolicScope()
                    shared = sigs.count(sigs[i]) > 1
                    auto = (lambda j: f"dyn{j}") if shared else \
                        (lambda j, _i=i: f"dyn{_i}_{j}")
                    shape_str = ", ".join(
                        d if isinstance(d, str)
                        else (str(d) if d is not None and d != -1
                              else auto(j))
                        for j, d in enumerate(dims))
                    dims = jexport.symbolic_shape(shape_str, scope=scope)
                specs.append(jax.ShapeDtypeStruct(dims, s.dtype))
            pv = [p._value for p in params]
            bv = [b._value for b in buffers]
            exported = jexport.export(jax.jit(pure))(
                [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pv],
                [jax.ShapeDtypeStruct(b.shape, b.dtype) for b in bv],
                *specs,
            )
            with open(path + ".pdmodel", "wb") as f:
                f.write(exported.serialize())
            meta["exported"] = True
            meta["n_inputs"] = len(specs)
        except Exception as e:  # export is best-effort; weights always saved
            meta["exported"] = False
            meta["export_error"] = str(e)
    with open(path + ".pdmeta", "wb") as f:
        pickle.dump(meta, f)


class TranslatedLayer:
    """The object ``jit.load`` returns (reference jit.TranslatedLayer):
    call-compatible with the original Layer, running the deserialized
    StableHLO program over the reloaded weights."""

    def __init__(self, state, meta, exported):
        self.state = state
        self._meta = meta
        self._exported = exported

    def __call__(self, *inputs):
        # reconstruct (params, buffers, *inputs) calling convention using
        # the key order recorded at save time (frozen params were baked
        # into the export and appear in neither list)
        pv = [self.state[k]._value
              for k in self._meta.get("param_keys", [])]
        bv = [self.state[k]._value
              for k in self._meta.get("buffer_keys", [])]
        ivals = [t._value if isinstance(t, Tensor) else jnp.asarray(t)
                 for t in inputs]
        outs = self._exported.call(pv, bv, *ivals)
        outs = [to_tensor(o) for o in outs]
        return outs[0] if len(outs) == 1 else tuple(outs)

    forward = __call__

    def eval(self):
        return self

    def train(self):
        return self


def load(path, **configs):
    from ..framework.io import load as fload

    state = fload(path + ".pdiparams")
    meta = {}
    if os.path.exists(path + ".pdmeta"):
        with open(path + ".pdmeta", "rb") as f:
            meta = pickle.load(f)
    # .stablehlo is the honesty-named artifact paddle.onnx.export writes
    # (same serialized jax.export payload as .pdmodel)
    for ext in (".pdmodel", ".stablehlo"):
        if os.path.exists(path + ext):
            from jax import export as jexport

            with open(path + ext, "rb") as f:
                exported = jexport.deserialize(f.read())

            return TranslatedLayer(state, meta, exported)
    raise InvalidArgumentError(
        f"No exported program at {path}.pdmodel or {path}.stablehlo — only "
        f"weights were saved (export_error: {meta.get('export_error')})"
    )


_DEBUG = {"code_level": 0, "verbosity": 0}


def set_code_level(level=100, also_to_stdout=False):
    """Debug knob (reference jit.set_code_level): level > 0 makes
    dy2static print the rewritten source of each converted function."""
    _DEBUG["code_level"] = int(level)


def set_verbosity(level=0, also_to_stdout=False):
    """Debug knob (reference jit.set_verbosity): level > 0 logs one line
    per dy2static-converted function (``also_to_stdout`` is accepted for
    signature compatibility; output already goes to stdout)."""
    _DEBUG["verbosity"] = int(level)


def ignore_module(modules):
    """No-op (AST transform exclusion list — no AST pass here)."""


def not_to_static(fn=None):
    return fn


_UNSET = object()  # "not scanned yet" sentinel (None = scanned, no mesh)


class _AOTCachedJit:
    """A jax.jit function plus an optional AOT-compiled executable.

    ``ensure_compiled(args)`` lowers+compiles without executing — and the
    executable lands in the pjit cache, so the compile work is paid exactly
    once whether or not the caller pre-compiled. Calls always go through
    the jitted function itself: its C++ dispatch path re-flattens the
    ~600-leaf param/state pytree in native code, where the stored
    ``Compiled`` object's Python call layer costs ~4 ms/step on a
    ResNet-50-sized parameter list (measured; the executable both paths
    run is the same one)."""

    def __init__(self, jitted):
        self._jitted = jitted
        self._compiled = None

    def ensure_compiled(self, *args):
        if self._compiled is None:
            self._compiled = self._jitted.lower(*args).compile()
        return self._compiled

    def __call__(self, *args):
        return self._jitted(*args)


class FusedTrainStep:
    """ONE compiled XLA program per optimization step: forward + loss +
    backward + optimizer update, with parameters/optimizer state in donated
    buffers.

    TPU-native rationale: the reference pays per-op launch costs and so
    splits compute/optimizer into streams; under XLA the whole step as a
    single program lets the compiler overlap everything AND costs exactly
    one host->device dispatch. This is the Layer/Optimizer-API
    counterpart of ``models.llama.make_sharded_train_step``.

    Usage::

        step = paddle.jit.fused_train_step(loss_fn, optimizer)  # or (model=)
        loss = step(x, y)          # params/opt state updated in place
    """

    def __init__(self, loss_fn: Callable, optimizer, model: Optional[Layer] = None,
                 has_aux: bool = False):
        self._loss_fn = loss_fn
        self._opt = optimizer
        self._has_aux = has_aux  # loss_fn returns (loss, aux...) — aux is
        # returned to the caller (e.g. logits for metrics) from the SAME
        # single compiled program
        if model is None:
            # discover the Layer through the closure like TracedProgram does
            # (buffers must ride the program as inputs, not baked constants)
            _, _, model = _collect_state(loss_fn)
        self._model = model
        self._cache: Dict[Any, Any] = {}
        self._const_key = None  # fixed key for randomness-free programs
        self._setup_cache = None  # (model, ids, params, ...) static state
        self._key_sharding = _UNSET  # lazily scanned from the param set
        register_compiled_cache(self)

    def cache_info(self) -> Dict[str, Any]:
        """Cache-key introspection (analysis.recompile): keys carry the
        arg tree, input shape signature, param-set identity, train/eval
        mode and the optimizer-kernel dispatch signature."""
        name = getattr(self._loss_fn, "__name__", "loss_fn")
        return {"name": f"fused_train_step:{name}",
                "keys": list(self._cache.keys())}

    def compiled_text(self, *inputs) -> str:
        """Optimized HLO of the step compiled for these inputs (the
        program-auditor entry point: compiles AOT, executes nothing)."""
        entry, _, call_tail = self._prepare(inputs)
        dummy_key = self._place_key(jax.random.key_data(jax.random.key(0)))
        compiled = entry.ensure_compiled(dummy_key, *call_tail)
        return compiled.as_text()

    def _state_setup(self):
        opt = self._opt
        params = opt._params()
        pid = tuple(id(p) for p in params)
        cached = self._setup_cache
        # the cache holds the param OBJECTS (cached[2]) purely to pin
        # their ids alive: while the entry exists no new Tensor can reuse
        # those addresses, so the id-tuple comparison alone is sound (the
        # unpinned form had a GC'd-params/id-reuse false-hit hazard)
        if (cached is None or cached[0] is not self._model
                or cached[1] != pid):
            # per-(model, param-set) constants: ensure_state walk, state-key
            # names, per-param extras (static decay coefficients), and the
            # model's buffer list (a sublayer walk that costs ~1 ms/call on
            # a ResNet-sized tree — params changing identity is the
            # invalidation signal, the same one the program cache keys on)
            for p in params:
                opt._ensure_state(p)
            state_keys = opt._state_names()
            evals = [opt._per_param_extras(p) for p in params]
            buffers = (self._model.buffers()
                       if self._model is not None else [])
            self._setup_cache = (self._model, pid, list(params),
                                 state_keys, evals, buffers)
            self._key_sharding = _UNSET  # param set changed: rescan mesh
            self._const_key = None
        else:
            _, _, _, state_keys, evals, buffers = cached
        svals = [{k: opt._accumulators[id(p)][k] for k in state_keys}
                 for p in params]
        return params, state_keys, svals, evals, buffers

    def compile(self, *inputs):
        """Trace + lower + compile the step for these input shapes WITHOUT
        executing it (no buffers donated, no RNG consumed, no optimizer
        state touched). Callers that want an eager fallback on *tracing*
        failures only — not on genuine runtime errors — compile() inside
        their try block and then __call__ outside it (hapi does this).
        The compiled executable is cached, so the following __call__ pays
        no second compilation."""
        entry, _, call_tail = self._prepare(inputs)
        dummy_key = self._place_key(jax.random.key_data(jax.random.key(0)))
        entry.ensure_compiled(dummy_key, *call_tail)
        return self

    def _place_key(self, key_data):
        """Replicate the RNG key onto the params' mesh when the model is
        GSPMD-sharded (``dist.shard_layer`` / NamedSharding params): jit
        rejects a single-device key next to mesh-placed arguments. The
        param scan is cached per param-set (``_key_sharding``, refreshed by
        ``_state_setup``) so the per-step cost is one device_put at most."""
        sh = self._key_sharding
        if sh is _UNSET:
            from jax.sharding import NamedSharding, PartitionSpec

            sh = None
            for p in (self._opt._params() if self._opt is not None else []):
                psh = getattr(p._value, "sharding", None)
                if isinstance(psh, NamedSharding) and \
                        psh.mesh.devices.size > 1:
                    sh = NamedSharding(psh.mesh, PartitionSpec())
                    break
            self._key_sharding = sh
        return key_data if sh is None else jax.device_put(key_data, sh)

    def _prepare(self, inputs):
        from ..framework import random as _random

        opt = self._opt
        params, state_keys, svals, evals, buffers = self._state_setup()
        tensor_args, arg_tree, rest_args, rest_kwargs = _split_args(inputs, {})
        ivals = [t._value for t in tensor_args]

        from ..ops.pallas.multi_tensor_update import fused_update_signature

        key = (_tree_key(arg_tree),
               tuple((tuple(v.shape), str(v.dtype)) for v in ivals),
               tuple(id(p) for p in params),  # unfreezing params recompiles
               getattr(self._model, "training", None),
               # optimizer-kernel dispatch state: a use_pallas_fused_update
               # flip mid-run must not reuse a program traced the other way
               fused_update_signature())
        jitted = self._cache.get(key)
        if jitted is None:
            loss_fn = self._loss_fn
            rest_args = ()  # _rebuild_args rebuilds from arg_tree alone;
            # capturing the caller's tensors would pin their device buffers
            swap_targets = list(params) + list(buffers)
            l2 = opt._l2_coeff
            decay_in_grad = opt._apply_weight_decay_to_grad()
            grad_clip = opt._grad_clip


            has_aux = self._has_aux
            rng_state = [False, False]  # [traced once, randomness consumed]

            def pure(key_data, pvals, bvals, svals_, evals_, lr_, step_,
                     *ivals_):
                def functional_loss(pvals_):
                    buf_writes: List[Any] = []
                    with _SwapValues(swap_targets,
                                     list(pvals_) + list(bvals)):
                        args, kwargs = _rebuild_args(arg_tree, ivals_,
                                                     rest_args, rest_kwargs)
                        _TRACING[0] = True
                        _BUFFER_COLLECTOR.append(buf_writes)
                        _random.push_trace_key(
                            jax.random.wrap_key_data(key_data))
                        try:
                            with autograd.no_grad():
                                out = loss_fn(*args, **kwargs)
                        finally:
                            rng_state[1] |= _random.pop_trace_key()
                            rng_state[0] = True
                            _BUFFER_COLLECTOR.pop()
                            _TRACING[0] = False
                    # buffer updates (BN running stats) must flow OUT through
                    # the differentiated function's aux — a side list would
                    # leak linearize-trace tracers
                    by_id = {id(t): v for t, v in buf_writes}
                    new_b_local = tuple(
                        jax.lax.stop_gradient(by_id[id(b)])
                        if id(b) in by_id else bv
                        for b, bv in zip(buffers, bvals))
                    if has_aux:
                        loss_t, *aux = out
                        aux_vals = tuple(
                            a._value if isinstance(a, Tensor) else a
                            for a in aux)
                    else:
                        loss_t, aux_vals = out, ()
                    return (loss_t._value.astype(jnp.float32),
                            (aux_vals, new_b_local))

                (loss, (aux, new_b)), grads = jax.value_and_grad(
                    functional_loss, has_aux=True)(list(pvals))
                if grad_clip is not None:
                    clipped = grad_clip(list(zip(params, grads)))
                    grads = [g for _, g in clipped]
                grads = [g.astype(pv.dtype) if g.dtype != pv.dtype else g
                         for pv, g in zip(pvals, grads)]
                if l2 and decay_in_grad:
                    grads = [g + l2 * pv for pv, g in zip(pvals, grads)]
                # multi-tensor fused update (flat-packed for elementwise
                # optimizers — see Optimizer.apply_updates): `evals` (the
                # closure's HOST scalars) key the static grouping, the
                # traced evals_ carry the values
                new_p, new_s = opt.apply_updates(
                    list(pvals), grads, svals_, evals_, evals, lr_, step_)
                return loss, aux, new_p, new_s, new_b

            jitted = _AOTCachedJit(jax.jit(pure, donate_argnums=(1, 3)))
            jitted.rng_state = rng_state
            self._cache[key] = jitted
            _obs_metrics.counter("jit.program_cache_misses").inc()
            _flight.record(
                "program_cache_miss",
                program=f"fused_train_step:"
                        f"{getattr(self._loss_fn, '__name__', 'loss_fn')}",
                entries=len(self._cache))

        bvals = [b._value for b in buffers]
        pvals = [p._value for p in params]
        # host scalars, NOT device arrays: an uncommitted scalar lets jit
        # place lr/step wherever the (possibly mesh-sharded) params live
        lr = np.float32(opt.get_lr())
        call_tail = (pvals, bvals, svals, evals, lr,
                     np.int32(opt._step_count + 1)) + tuple(ivals)
        return jitted, (params, buffers), call_tail

    def __call__(self, *inputs):
        from ..framework.random import next_key

        opt = self._opt
        jitted, (params, buffers), call_tail = self._prepare(inputs)
        # the per-step key split costs ~1 ms of host time on big parameter
        # lists; once the trace proved the model consumes no randomness
        # (no dropout etc.), reuse one fixed key instead of splitting
        traced, consumed = getattr(jitted, "rng_state", (False, True))
        if traced and not consumed:
            key_data = self._const_key
            if key_data is None:
                key_data = self._const_key = self._place_key(
                    jax.random.key_data(jax.random.key(0)))
        else:
            key_data = self._place_key(jax.random.key_data(next_key()))
        # step count rides as data; committed only after a successful call so
        # a failed trace doesn't skew bias correction for an eager fallback
        loss, aux, new_p, new_s, new_b = jitted(key_data, *call_tail)
        from ..ops.dispatch import note_dispatch

        note_dispatch(loss)  # Stream/Event.query honesty for the fused path
        opt._step_count += 1
        # the optimizer update is INSIDE this program, so the step
        # counter ticks here (Optimizer.step() never runs on this path)
        _obs_metrics.counter("optimizer.steps").inc()
        _obs_metrics.gauge("optimizer.lr").set(float(call_tail[4]))
        for p, np_, ns_ in zip(params, new_p, new_s):
            p._inplace_set(np_)
            opt._accumulators[id(p)] = ns_
        for b, nb in zip(buffers, new_b):
            if nb is not b._value:
                b._inplace_set(nb)
        loss_t = Tensor(loss, stop_gradient=True)
        if self._has_aux:
            return (loss_t,) + tuple(Tensor(a, stop_gradient=True)
                                     for a in aux)
        return loss_t


def fused_train_step(loss_fn=None, optimizer=None, model=None,
                     has_aux=False):
    """Build a one-dispatch-per-step compiled training function."""
    return FusedTrainStep(loss_fn, optimizer, model, has_aux=has_aux)


_to_static_enabled = True


def enable_to_static(enable: bool = True) -> None:
    """Globally toggle ``@to_static`` compilation (reference:
    ``paddle.jit.enable_to_static``) — with it off, decorated functions run
    eagerly (debugging aid)."""
    global _to_static_enabled
    _to_static_enabled = bool(enable)
