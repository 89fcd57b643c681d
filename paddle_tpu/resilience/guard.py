"""Non-finite step guard: in-graph skip of NaN/Inf steps.

One NaN step poisons every parameter it touches and, through Adam's
moments, every later step — on a TPU fleet the blow-up typically lands
long after its cause. ``StepGuard`` makes the bad step a bitwise no-op
INSIDE the compiled step: it computes a finite-ness predicate over the
loss and every gradient, lets the optimizer update run, then
where-blends every written slot (params, master weights, accumulators)
back to its pre-step snapshot when the predicate is false. No host
sync, no recompile, no control flow the tracer can't see — the skip is
a handful of selects fused into the step program.

A device-side consecutive-bad-step counter threads through the compiled
step as ordinary captured state; the host consults it lazily (only when
it already observed a non-finite loss) and raises a coded
``NonFiniteStepError`` once the budget is exceeded. With an
``amp.GradScaler`` attached, each observed bad step also backs the loss
scale off, the reference's dynamic-loss-scaling response.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from ..core.errors import NonFiniteStepError
from ..core.tensor import Tensor

__all__ = ["StepGuard"]


class StepGuard:
    """Guard a train step against non-finite loss/grads.

    hapi wiring: ``Model.prepare(..., step_guard=StepGuard())`` (or
    ``step_guard=True``). Custom loops::

        guard = StepGuard(max_bad_steps=3, scaler=scaler)
        loss = loss_fn(...)
        loss.backward()
        guard.guarded_step(opt, loss)   # skips the update when bad
        opt.clear_grad()
        guard.observe(float(loss))      # host: backoff + budget raise

    ``max_bad_steps`` consecutive bad steps are skipped silently; the
    next one raises ``NonFiniteStepError`` (PDT-E013).

    A step can be bad with a FINITE loss (bf16/fp16 overflow in the
    backward pass only) — the host never sees that in the loss scalar,
    so ``observe`` additionally syncs the device streak counter every
    ``grad_sync_every`` good-looking steps; without it a run could
    skip every step bitwise forever while reporting healthy losses.
    """

    def __init__(self, max_bad_steps=3, scaler=None, grad_sync_every=32):
        self.max_bad_steps = int(max_bad_steps)
        self._scaler = scaler
        self.grad_sync_every = max(1, int(grad_sync_every))
        # created HERE so jit capture classifies it as persistent state
        # (input + output of the compiled step), not a step temporary
        self._streak_var = Tensor(jnp.zeros((), jnp.int32))
        self._host_streak = 0
        self._observed = 0
        self.last_skipped = False

    # ------------------------------------------------------------ traced --
    def check(self, loss, optimizer=None):
        """Finite-ness predicate (0-d bool) over the loss and, when an
        optimizer is given, every gradient it would consume."""
        vals = [loss._read() if isinstance(loss, Tensor) else loss]
        if optimizer is not None:
            for _p, g in optimizer._collect():
                vals.append(g._read())
        ok = jnp.asarray(True)
        for v in vals:
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(v)))
        return ok

    def guarded_step(self, optimizer, loss):
        """``optimizer.step()`` that is a bitwise no-op when the step is
        bad. Returns the predicate (traced)."""
        ok = self.check(loss, optimizer)

        # snapshot every slot the update may write: params with grads,
        # their master weights, and all existing accumulators. On the
        # fused multi-tensor path the flat bucket STORAGES are the
        # written slots (optimizer/flat.py) — under jit capture the
        # per-param views are skipped (the compiled program threads
        # only the storages; blending them is a handful of selects
        # instead of O(params)). EAGERLY the views are snapshotted too:
        # a FlatMismatch can defuse the buckets mid-step, and the
        # per-param fallback's writes must still roll back.
        from ..core import tensor as _tm
        snaps = []
        capturing = _tm._tracker is not None

        def _skip(t):
            fv = t._flat_view
            return capturing and fv is not None and fv[1] >= 0
        fused_slots = getattr(optimizer, "_fused_guard_slots", None)
        if fused_slots is not None:
            for t in fused_slots():
                snaps.append((t, t._read()))
        for p, _g in optimizer._collect():
            if not _skip(p):
                snaps.append((p, p._read()))
            mw = optimizer._master_weights.get(id(p))
            if mw is not None and not _skip(mw):
                snaps.append((mw, mw._read()))
        for store in optimizer._accumulators.values():
            for t in store.values():
                if not _skip(t):
                    snaps.append((t, t._read()))

        # accumulators/master weights born DURING this step (only the
        # first-ever optimizer step) blend back to their creation value
        created = []
        orig_acc = optimizer._acc
        orig_master = optimizer._get_master

        def patched_acc(name, p, **kw):
            store = optimizer._accumulators.setdefault(name, {})
            fresh = id(p) not in store
            val = orig_acc(name, p, **kw)
            if fresh:
                created.append((store[id(p)], val))
            return val

        def patched_master(p):
            fresh = id(p) not in optimizer._master_weights
            val = orig_master(p)
            if fresh:
                created.append((optimizer._master_weights[id(p)], val))
            return val

        optimizer._acc = patched_acc
        optimizer._get_master = patched_master
        # flat bucket storages born during THIS step (the first fused
        # step builds them) blend back to their creation values, the
        # same first-step contract as patched_acc above
        optimizer._flat_created_log = created
        try:
            optimizer.step()
        finally:
            del optimizer._acc
            del optimizer._get_master
            optimizer._flat_created_log = None

        for t, snap in snaps + created:
            fv = t._flat_view
            if fv is not None and fv[1] >= 0:
                # still a bound flat view at blend time: its bucket
                # storage is itself in the blend set (snapshotted via
                # _fused_guard_slots, or in the created log when born
                # this step) and the view reads through it lazily — a
                # direct write would mark a local override and force a
                # full per-member re-sync of the bucket next step. The
                # view snapshots matter only when a mid-step defuse
                # unbound them, in which case fv is cleared and the
                # write below runs.
                continue
            cur = t._read()
            t._write(jnp.where(ok, cur, snap))

        streak = self._streak_var._read()
        self._streak_var._write(
            jnp.where(ok, jnp.zeros((), jnp.int32), streak + 1))
        return ok

    # -------------------------------------------------------------- host --
    @property
    def bad_streak(self) -> int:
        """Device-side consecutive-bad-step count (host sync; don't call
        from traced code)."""
        return int(np.asarray(self._streak_var._read()))

    def observe(self, loss_value) -> bool:
        """Host-side bookkeeping with the already-fetched loss scalar.
        Returns True when the step was bad. Backs off the attached
        ``GradScaler`` and raises ``NonFiniteStepError`` once MORE than
        ``max_bad_steps`` consecutive steps were bad."""
        self._observed += 1
        bad = not math.isfinite(float(loss_value))
        if not bad and self._observed % self.grad_sync_every == 0:
            # periodic device sync catches grad-only non-finite steps
            # (finite loss, overflowed grads) the loss scalar hides
            bad = self.bad_streak > 0
        if not bad:
            self._host_streak = 0
            self.last_skipped = False
            return False
        self._host_streak += 1
        self.last_skipped = True
        # observability breadcrumbs: the skip streak in the event ring
        # (flight records show the NaN steps preceding a blow-up) and a
        # process-global counter a dashboard can alert on
        from ..observability import events as _events
        from ..observability import metrics as _metrics
        _events.emit("guard.step_skip", streak=self._host_streak)
        _metrics.registry().counter(
            "train.guard_skips",
            "non-finite train steps skipped in-graph by StepGuard").inc()
        if self._scaler is not None and self._scaler.is_enable():
            # the reference GradScaler response: shrink the loss scale
            self._scaler._found_inf = True
            self._scaler._update_scale()
            self._scaler._found_inf = False
        # the device streak also counts bad-grads/finite-loss steps the
        # host never saw; consult it only now that a sync is warranted
        streak = max(self._host_streak, self.bad_streak)
        if streak > self.max_bad_steps:
            raise NonFiniteStepError(
                f"{streak} consecutive non-finite training steps "
                f"(budget {self.max_bad_steps}); every one was skipped, "
                "parameters are still finite. Lower the learning rate, "
                "check the input pipeline for bad records, or enable "
                "loss scaling (amp.GradScaler) if training in fp16. "
                f"[{NonFiniteStepError.error_code}]")
        return True
