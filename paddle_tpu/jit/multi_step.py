"""Multi-step execution: K train steps as ONE device program.

TPU-native counterpart of the reference's dataloader+executor step loop:
under a single-controller with a network-attached chip every executable
launch pays a host round trip (the PJRT-client analog of kernel-launch
overhead). ``multi_step`` folds a window of K steps of an already-captured
``jit.to_static`` function into one ``lax.scan``: the per-step state
(params, optimizer moments, RNG) threads through the scan carry entirely
on-device, batches are fed as stacked scan inputs, and only the final
state and the per-step outputs return to the host. Step-time overhead
drops from O(K) round trips to O(1). ``WindowRunner`` additionally
hoists the remaining per-window host work (input staging, output
slicing) out of the steady-state path.

Constraints: every step must hit the SAME compiled specialization (same
shapes/dtypes/modes), and host-side hooks that normally run between steps
(LR-scheduler sync) apply once for the window — `.step()` the scheduler
K times afterwards, as the training loop already does per batch.

With the fused multi-tensor optimizer (``optimizer/flat.py``) the scan
carry holds a handful of flat dtype buckets (params, master weights,
moments, grads) instead of hundreds of per-param arrays: the capture
filters bucket member views out of its state (``jit/__init__.py``), so
the window program's carry — and its donation set — is O(buckets).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor


def _resolve_exe(static_fn, first):
    """(exe, out0) for the specialization of ``first`` — compiling it
    with one eager-dispatched step (whose output is returned as
    ``out0``) if this is the first call."""
    if hasattr(static_fn, "_cache"):           # StaticFunction itself
        wrapped = static_fn
    else:                                      # bound-method partial
        wrapped = getattr(static_fn, "__wrapped__", None)
    if wrapped is None or not hasattr(wrapped, "_cache"):
        raise TypeError("multi_step expects a jit.to_static function")
    key = wrapped._cache_key(first, {})
    exe = wrapped._cache.get(key)
    out0 = None
    if exe is None:
        out0 = static_fn(*first)
        exe = wrapped._cache.get(key)
    if exe is None:
        raise RuntimeError(
            "step did not compile (eager fallback) — multi_step needs the "
            "compiled path; fix the graph break first")
    return exe, out0


def _split(exe, per_step_idx=()):
    """(carry_idx, const_idx, ps_idx) into ``exe.capt_state`` — the ONE
    place the promoted per-step indices are removed from the constants,
    shared by the window builder and the runner so their orderings can
    never drift apart."""
    carry_idx, const_idx = exe.state_split()
    ps_idx = list(per_step_idx)
    return carry_idx, [i for i in const_idx if i not in ps_idx], ps_idx


def _build_window(exe, donate, per_step_idx=()):
    """The jitted K-step window program for ``exe``: scan the step's pure
    function over stacked inputs, threading the written captured state
    through the (donated) carry and closing over the read-only state.

    ``per_step_idx``: indices into ``exe.capt_state`` promoted from scan
    constants to PER-STEP scanned inputs (leading [K] axis) — the
    mechanism behind per-step learning rates inside a window (a captured
    LR scalar is otherwise frozen for all K steps because its host-side
    scheduler sync runs once per launch, not once per step)."""
    capt = exe.capt_state
    n_state = len(exe.state_out_tensors)
    n_ret = exe.n_ret
    carry_idx, const_idx, ps_idx = _split(exe, per_step_idx)
    pure = exe._pure

    def window(carry_vals, const_vals, ps_stacks, *stacks):
        def body(carry, xs):
            ps_vals, arg_vals = xs
            state = [None] * len(capt)
            for i, v in zip(carry_idx, carry):
                state[i] = v
            for i, v in zip(const_idx, const_vals):
                state[i] = v
            for i, v in zip(ps_idx, ps_vals):
                state[i] = v
            outs = pure(*arg_vals, *state)
            return (list(outs[n_ret:n_ret + n_state]),
                    tuple(outs[:n_ret]))

        carry, rets = jax.lax.scan(body, list(carry_vals),
                                   (tuple(ps_stacks), tuple(stacks)))
        return carry, rets

    return jax.jit(window, donate_argnums=(0,) if donate else ())


def _run_window(exe, runner, stacks, per_step_idx=(), per_step_vals=()):
    """Execute one window: read the captured state, launch, write the
    post-window state back. Returns the stacked per-step outputs."""
    capt = exe.capt_state
    carry_idx, const_idx, ps_idx = _split(exe, per_step_idx)
    for sync in exe.discovery.host_syncs:
        sync()
    from . import _state_write
    carry_vals = [capt[i]._read() for i in carry_idx]
    const_vals = [capt[i]._read() for i in const_idx]
    # whole-program audit of the window once per runner (compile-time
    # only; make_jaxpr does not consume the soon-to-be-donated carry)
    audited = exe.__dict__.setdefault("_window_audit_done", set())
    if id(runner) not in audited:
        audited.add(id(runner))
        from .. import analysis as _analysis
        _analysis.audit_jitted(
            runner,
            (carry_vals, const_vals, tuple(per_step_vals)) + tuple(stacks),
            where=f"multi_step.{getattr(exe, '_fn_name', 'window')}")
    final_carry, rets = runner(carry_vals, const_vals,
                               tuple(per_step_vals), *stacks)
    for i, v in zip(carry_idx, final_carry):
        _state_write(capt[i], v)
    # leave the promoted tensors holding their LAST per-step value, as
    # if the host had fed each step individually
    for i, v in zip(ps_idx, per_step_vals):
        _state_write(capt[i], v[-1])
    return rets


class WindowRunner:
    """A K-step training window as ONE dispatch with pre-staged inputs.

    ``multi_step`` pays per-window host work, each piece at the latency
    of a dispatch: a separate single-step dispatch for the
    first batch, per-window ``jnp.stack`` calls, and one device-slice
    dispatch per step to rebuild outputs. ``WindowRunner`` hoists all of
    it out of the steady-state path: ``stage()`` uploads a whole window
    of batches as stacked arrays once; ``run()`` is then exactly one
    compiled scan launch over all K steps (params/moments/RNG donated
    through the carry) returning the per-step outputs device-resident.

    Usage::

        w = WindowRunner(train_step, example_args, length=K)
        stacks = w.stage(batches)        # K host batches -> device
        losses = w.run(*stacks)          # ONE dispatch, K steps
        last = float(losses[-1])         # sync / readback

    NOTE: if ``static_fn`` has not yet compiled for this signature,
    construction primes it by executing ONE real step on
    ``example_args`` — exactly the state mutation of calling the step
    once. Construct after warmup (the usual case) to avoid it.
    """

    def __init__(self, static_fn, example_args, length, donate=True,
                 per_step=None):
        if length < 1:
            raise ValueError("window length must be >= 1")
        self.length = length
        first = tuple(example_args)
        exe, _ = _resolve_exe(static_fn, first)
        self._exe = exe
        self._n_args = len(first)
        self._ps_idx = []
        if per_step:
            pos = {id(t): i for i, t in enumerate(exe.capt_state)}
            carry = set(exe.state_split()[0])
            for t in per_step:
                i = pos.get(id(t))
                if i is None:
                    raise ValueError(
                        "per_step tensor is not captured state of this "
                        "step (it must be read by the compiled function)")
                if i in carry:
                    raise ValueError(
                        "per_step tensor is WRITTEN by the step — it "
                        "already threads through the scan carry")
                self._ps_idx.append(i)
        self._runner = _build_window(exe, donate, tuple(self._ps_idx))

    def stage(self, arg_batches):
        """Stack a window of batches into device arrays (one upload per
        argument position). Call outside the timed/steady-state path;
        the result can be reused across ``run`` calls (e.g.
        benchmarking) or double-buffered against the previous window's
        execution.

        Batches already resident on device (the common fit-loop case:
        DataLoader collate built device tensors) are stacked ON DEVICE
        — ``np.stack`` over device arrays would round-trip every batch
        through the host, once for each batch of the window, where the
        device-side stack is one program."""
        import numpy as np
        if len(arg_batches) != self.length:
            raise ValueError(
                f"expected {self.length} batches, got {len(arg_batches)}")
        cols = []
        for i in range(self._n_args):
            vals = [b[i]._read() if isinstance(b[i], Tensor) else b[i]
                    for b in arg_batches]
            if all(isinstance(v, jax.Array) for v in vals):
                cols.append(jnp.stack(vals))
            else:
                cols.append(jnp.asarray(np.stack(
                    [np.asarray(v) for v in vals])))
        return tuple(cols)

    def run(self, *stacks, outputs="all", per_step_vals=None):
        """One compiled K-step launch. Returns the per-step outputs as a
        list of ``length`` entries (device-resident until read); captured
        state (params, moments, RNG) holds the post-window values.

        ``outputs``: "all" rebuilds every step's outputs (one device
        slice per step); "last" only the final step's (the common
        train-loop need — logging the latest loss — at one slice);
        "stacked" returns the raw [K, ...] arrays with no slicing.

        ``per_step_vals``: one [length, ...] array per ``per_step``
        tensor declared at construction — that tensor takes value
        ``per_step_vals[j][k]`` during step k (e.g. a warmup LR ramp
        inside the window)."""
        exe = self._exe
        if len(per_step_vals or ()) != len(self._ps_idx):
            raise ValueError(
                f"expected {len(self._ps_idx)} per_step_vals arrays, "
                f"got {len(per_step_vals or ())}")
        ps_vals = tuple(jnp.asarray(v) for v in per_step_vals or ())
        for v in ps_vals:
            n = v.shape[0] if v.ndim else -1
            if n != self.length:
                raise ValueError(
                    f"per_step_vals arrays need leading dim "
                    f"{self.length}, got {n}")
        rets = _run_window(exe, self._runner, stacks, self._ps_idx,
                           ps_vals)
        if outputs == "stacked":
            return rets
        if outputs == "last":
            step_ret = [Tensor(r[-1]) for r in rets]
            return exe.ret_rebuild(step_ret)
        outs = []
        for s in range(self.length):
            step_ret = [Tensor(r[s]) for r in rets]
            outs.append(exe.ret_rebuild(step_ret))
        return outs

    def rebuild_host(self, rets):
        """``run(..., outputs="stacked")`` results -> list of per-step
        output structures over HOST-resident tensors: ONE device
        readback per output leaf (each ``outputs="all"`` step slice is
        a separate dispatch — ~3-12 ms each over a network-attached
        chip; reading the stacked arrays once amortizes that to one
        round trip per leaf for the whole window)."""
        import numpy as np
        host = [np.asarray(r) for r in rets]
        outs = []
        for s in range(self.length):
            step_ret = [Tensor(h[s]) for h in host]
            outs.append(self._exe.ret_rebuild(step_ret))
        return outs


def multi_step(static_fn, arg_batches: Sequence[Sequence], donate=True):
    """Run ``static_fn`` (a ``@jit.to_static`` function) over
    ``arg_batches`` — a sequence of per-step positional-arg tuples with
    identical shapes — in one compiled scan. Returns the list of per-step
    outputs (device-resident until read). State tensors captured by the
    step (parameters, moments, RNG) hold the post-window values, exactly
    as if the steps had been dispatched one by one.

    The first batch always runs as a single eager-dispatched step (it is
    also the compile trigger on first use); the remaining K-1 batches run
    as one scanned window. For a steady-state loop where even that
    per-window work matters, use :class:`WindowRunner`."""
    if not arg_batches:
        return []
    first = tuple(arg_batches[0])
    exe, out0 = _resolve_exe(static_fn, first)
    if out0 is None:  # already compiled — still dispatch the first batch
        out0 = static_fn(*first)
    rest = [tuple(b) for b in arg_batches[1:]]
    if not rest:
        return [out0]

    n_args = len(first)
    cache = getattr(exe, "_multi_step_cache", None)
    if cache is None:
        cache = exe._multi_step_cache = {}
    runner = cache.get((len(rest), donate))
    if runner is None:
        runner = cache[(len(rest), donate)] = _build_window(exe, donate)

    stacks = tuple(
        jnp.stack([jnp.asarray(b[i]._read() if isinstance(b[i], Tensor)
                               else b[i]) for b in rest])
        for i in range(n_args))
    rets = _run_window(exe, runner, stacks)
    outs = [out0]
    for s in range(len(rest)):
        step_ret = [Tensor(r[s]) for r in rets]
        outs.append(exe.ret_rebuild(step_ret))
    return outs
