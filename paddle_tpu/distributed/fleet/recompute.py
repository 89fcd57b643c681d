"""Activation recompute (gradient checkpointing).

Capability analog of ``python/paddle/distributed/fleet/recompute/
recompute.py:404`` (SURVEY D19): trade FLOPs for activation memory by
re-running a block's forward during backward. TPU-native mechanism: the
reference re-executes the Python block under a preserved RNG state; here the
block is lifted into one ``jax.checkpoint``-wrapped pure function over
(tensor args + the block's parameters), so XLA itself rematerializes inside
the compiled program, where the tape linearises the block in the forward
pass and the policy's residuals are what the forward keeps — in eager it
shortens the tape's saved residuals to just the block inputs.

Limitation: stateful side effects inside the block (BatchNorm running
stats, RNG-consuming dropout) are not threaded out of the checkpointed
region — matching LLM-pretrain usage (dropout=0). Use ``jit.to_static``
around the full step for peak effect.
"""
from __future__ import annotations

import jax

from ...core import tensor as tensor_mod
from ...core.autograd import no_grad
from ...core.dispatch import apply
from ...core.tensor import Tensor


class _SubstituteTracker:
    """Maps a chosen set of tensors to trace-time values; everything else
    chains to the enclosing tracker (a jit capture, or none)."""

    def __init__(self, mapping, outer):
        self.map = mapping
        self.outer = outer
        self.writes: dict[int, object] = {}

    def on_create(self, t):
        if self.outer is not None:
            self.outer.on_create(t)

    def on_read(self, t):
        tid = id(t)
        if tid in self.map:
            return self.map[tid]
        if tid in self.writes:
            return self.writes[tid]
        if self.outer is not None:
            return self.outer.on_read(t)
        return t._data

    def substituted(self, t):
        """True when this tracker (or an enclosing one) holds a value
        of its own for ``t`` — a fused-optimizer member view must then
        read through the tracker, not slice its flat storage
        (optimizer/flat.py ``member_read``)."""
        if id(t) in self.map or id(t) in self.writes:
            return True
        outer = getattr(self.outer, "substituted", None)
        return outer is not None and outer(t)

    def on_write(self, t, val):
        # swallowed: values born inside jax.checkpoint must not escape the
        # trace through framework state (they would be leaked tracers)
        self.writes[id(t)] = val

    def on_grad_write(self, t):
        pass

    def add_host_sync(self, fn):
        if self.outer is not None:
            self.outer.add_host_sync(fn)


class _ReadRecorder:
    """Records which pre-existing Tensors a callable reads (to discover the
    parameters of a plain function/lambda passed to ``recompute``); writes
    are swallowed exactly like the substitute tracker so the probe run has
    no side effects on framework state."""

    def __init__(self, outer):
        self.outer = outer
        self.reads: list[Tensor] = []
        self._seen: set[int] = set()
        self._fresh: set[int] = set()
        self.writes: dict[int, object] = {}

    def on_create(self, t):
        self._fresh.add(id(t))
        if self.outer is not None:
            self.outer.on_create(t)

    def on_read(self, t):
        tid = id(t)
        if tid in self.writes:
            return self.writes[tid]
        if tid not in self._fresh and tid not in self._seen:
            self._seen.add(tid)
            self.reads.append(t)
        if self.outer is not None:
            return self.outer.on_read(t)
        return t._data

    def on_write(self, t, val):
        self.writes[id(t)] = val

    def on_grad_write(self, t):
        pass

    def add_host_sync(self, fn):
        pass


def _discover_params(function, args, kwargs):
    """Differentiable parameters read by ``function``: from the owning
    Layer when bound, else from a side-effect-free probe run (its outputs
    are unused, so under jit the probe is dead code XLA removes)."""
    owner = getattr(function, "__self__", None)
    if hasattr(owner, "parameters"):
        return [p for p in owner.parameters() if not p.stop_gradient]
    if hasattr(function, "parameters"):  # a Layer passed directly
        return [p for p in function.parameters() if not p.stop_gradient]
    cached = getattr(function, "_pdtpu_recompute_params", None)
    if cached is not None:
        return cached
    rec = _ReadRecorder(tensor_mod._tracker)
    old = tensor_mod.set_tracker(rec)
    try:
        with no_grad():
            function(*args, **kwargs)
    finally:
        tensor_mod.set_tracker(old)
    params = [t for t in rec.reads if not t.stop_gradient]
    # Cache on the function object: a reused callable probes only once.
    # (A lambda recreated every step re-probes — under jit.to_static the
    # probe is dead code XLA removes, but in pure-eager loops prefer a bound
    # Layer method, which skips probing entirely.)
    try:
        function._pdtpu_recompute_params = params
    except AttributeError:
        pass
    return params


def _dots_and_kernels_saveable(prim, *_, **__):
    """dots_saveable + custom (Pallas) kernel calls: ``dots_saveable``
    matches only dot_general, so under it ``jax.checkpoint`` runs a
    flash-attention forward inside the block again in the backward.
    Marking custom/pallas calls saveable keeps their outputs (``o`` and
    the log-sum-exp) as residuals instead; the extra HBM is one
    [B,S,H,D] activation per layer.

    A policy decides what ``jax.checkpoint`` keeps from the forward it
    is linearised on, so it holds for the forward PASS only where the
    block is linearised there: under a capture, where
    ``core/dispatch.py`` builds the block's vjp as it is recorded.  (On
    the v5e the kernel, fc1 + GELU and attn/proj ran twice a step while
    a captured block was linearised at backward time, whatever this
    policy named: PERF.md, PR 26/27.)  In eager the tape keeps only
    the block's inputs and the backward's ``jax.vjp`` runs the block
    again."""
    import jax as _jax
    if _jax.checkpoint_policies.dots_saveable(prim, *_, **__):
        return True
    return prim.name in ("pallas_call", "custom_vjp_call",
                         "custom_vjp_call_jaxpr")


def _named_saveable():
    import jax as _jax
    return _jax.checkpoint_policies.save_only_these_names(
        "ln_out", "act_out")


_NAMED_SAVEABLE = None


def _transformer_saveable(prim, *a, **k):
    """dots + kernels + the named transformer activations (ln_out /
    act_out, tagged via ``jax.ad_checkpoint.checkpoint_name`` in
    F.layer_norm and F.gelu): the backward reads the saved normed
    activations and GELU outputs instead of re-running the reductions
    and transcendentals. It was slower than dots_and_kernels where
    rounds 1-5 read it (GPT-124M width: the saved GELU residuals
    cost more HBM traffic than their recompute) — this is a memory/
    recompute KNOB, not a default. Called once per jaxpr eqn, so the
    underlying policy object is built once."""
    global _NAMED_SAVEABLE
    if _NAMED_SAVEABLE is None:
        _NAMED_SAVEABLE = _named_saveable()
    if _NAMED_SAVEABLE(prim, *a, **k):
        return True
    return _dots_and_kernels_saveable(prim, *a, **k)


_POLICIES = {
    None: None,
    "full": None,  # rematerialize everything (reference behavior)
    # save EVERY residual — zero recompute work in backward. The
    # checkpoint region still exists, which makes this the remat-OFF
    # anchor for bitwise A/B: policies differ only in which residuals
    # the backward reads saved vs recomputes, never in the math, so
    # grads across the whole spectrum (everything_saveable .. full)
    # are bitwise-identical (tests/test_train_perf.py). The eager
    # per-op tape sits OUTSIDE this family: its cotangent accumulation
    # order differs from a region vjp by ~1e-10 ulps (test_models.py
    # compares it at tolerance for that reason).
    "everything_saveable": "everything_saveable",
    # save MXU matmul outputs, recompute only elementwise ops — trades a
    # little HBM for skipping the expensive half of the re-forward
    "dots_saveable": "dots_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
    # dots + Pallas custom calls (flash attention) saveable: skips the
    # in-backward re-run of the attention forward kernel
    "dots_and_kernels_saveable": _dots_and_kernels_saveable,
    # + named ln/gelu activations (see _transformer_saveable)
    "transformer_saveable": _transformer_saveable,
}


def recompute(function, *args, use_reentrant=True, preserve_rng_state=True,
              policy=None, **kwargs):
    """Run ``function(*args)`` with its activations rematerialized in
    backward. ``function`` may be a bound ``Layer`` method (parameters come
    from the owning layer), a ``Layer``, or any callable (parameters are
    discovered by a probe run); they are threaded as explicit
    differentiable inputs of the checkpointed region.

    ``policy`` (TPU extension over the reference signature): a
    ``jax.checkpoint_policies`` name — "full" (default, the reference's
    recompute-everything), or "dots_saveable" to keep matmul outputs and
    recompute only the cheap elementwise ops."""
    params = _discover_params(function, args, kwargs)
    tensor_args = [a for a in args if isinstance(a, Tensor)]
    arg_ids = {id(a) for a in tensor_args}
    params = [p for p in params if id(p) not in arg_ids]
    all_inputs = tensor_args + params

    def run_block(*vals):
        mapping = {id(t): v for t, v in zip(all_inputs, vals)}
        sub = _SubstituteTracker(mapping, tensor_mod._tracker)
        old = tensor_mod.set_tracker(sub)
        try:
            with no_grad():
                out = function(*args, **kwargs)
        finally:
            tensor_mod.set_tracker(old)
        if isinstance(out, Tensor):
            return sub.writes.get(id(out), out._data)
        return tuple(sub.writes.get(id(o), o._data)
                     for o in out if isinstance(o, Tensor))

    if policy not in _POLICIES:
        raise ValueError(f"unknown recompute policy {policy!r}; "
                         f"one of {sorted(k for k in _POLICIES if k)}")
    pol_name = _POLICIES[policy]
    if callable(pol_name):
        pol = pol_name
    else:
        pol = (getattr(jax.checkpoint_policies, pol_name) if pol_name
               else None)
    ckpt = jax.checkpoint(run_block, policy=pol)
    return apply("recompute", lambda *vals: ckpt(*vals), *all_inputs)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """Reference ``recompute_sequential``: checkpoint a Sequential in
    segments. ``ctx`` = {"segments": k}."""
    segments = int(ctx.get("segments", 1)) if isinstance(ctx, dict) else 1
    layers = list(functions)
    if segments <= 1:
        chunks = [layers]
    else:
        per = max(1, len(layers) // segments)
        chunks = [layers[i:i + per] for i in range(0, len(layers), per)]

    out = args[0] if len(args) == 1 else args

    class _Seg:
        """Bound-method shim so recompute() can discover the chunk params."""

        def __init__(self, seg_layers):
            self._layers = seg_layers

        def parameters(self):
            ps = []
            for l in self._layers:
                ps.extend(l.parameters())
            return ps

        def __call__(self, x):
            for l in self._layers:
                x = l(x)
            return x

    for chunk in chunks:
        seg = _Seg(chunk)
        fn = seg.__call__  # bound: __self__ is seg (has .parameters())
        out = recompute(fn, out, **kwargs)
    return out


def recompute_hybrid(ctx, function, *args, **kwargs):
    """Reference ``recompute_hybrid.py:250`` (PP-aware offload variant);
    offload knobs are no-ops on TPU (XLA owns residual placement)."""
    return recompute(function, *args, **kwargs)
