"""Optimizer base + the paddle optimizer family.

Analog of ``python/paddle/optimizer/optimizer.py:103`` (reference) and its
subclasses (adam.py, adamw.py, momentum.py, ...). TPU-native details:

- accumulators are jax.Arrays updated with pure jnp math through the
  Tensor ``_read``/``_write`` funnel, so a jit-captured train step folds the
  whole optimizer into the single compiled XLA program (the reference fuses
  this per-op with multi_tensor / fused CUDA kernels — XLA does it for us);
- ``multi_precision`` keeps float32 master weights for bf16/fp16 params,
  matching the reference's master-weight behavior under AMP-O2.
"""
from __future__ import annotations

import warnings
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..core import scope as _scope
from ..core import state as _state
from ..core import tensor as _tm
from ..core.tensor import Parameter, Tensor
from ..nn.clip import ClipGradBase, ClipGradByGlobalNorm
from . import flat as _flat
from .lr import LRScheduler


class L2Decay:
    """paddle.regularizer.L2Decay analog."""

    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


class L1Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


def _one_number(key, arr):
    """A saved bias-correction power as the 0-d float32 it is kept as,
    whatever shape it was saved at: 0-d, ``[1]`` (the reference
    framework's) or a parameter's full shape (this one's older
    checkpoints)."""
    a = np.asarray(arr, np.float32).ravel()
    if a.size == 0 or not np.all(a == a[0]):
        raise ValueError(
            f"{key}: a bias-correction power is one number a parameter, "
            f"and the saved array of shape {tuple(np.shape(arr))} holds "
            f"{'none' if a.size == 0 else 'several'}")
    return jnp.float32(a[0])


class Optimizer:
    # accumulators that are one number a parameter (``beta ** steps``)
    _SCALAR_ACCS = ("beta1_pow", "beta2_pow")

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        if parameters is None:
            raise ValueError(
                "parameters is required in dygraph mode (pass "
                "model.parameters())")
        if isinstance(parameters, (Parameter, Tensor)):
            parameters = [parameters]
        parameters = list(parameters)
        if parameters and isinstance(parameters[0], dict):
            self._param_groups = parameters
            self._parameters = [p for g in parameters
                                for p in g["params"]]
        else:
            self._param_groups = [{"params": parameters}]
            self._parameters = parameters
        self._learning_rate = learning_rate
        if weight_decay is None:
            self._regularization = None
        elif isinstance(weight_decay, (L1Decay, L2Decay)):
            self._regularization = weight_decay
        else:
            self._regularization = L2Decay(float(weight_decay))
        assert grad_clip is None or isinstance(grad_clip, ClipGradBase)
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators: dict[str, dict[int, Tensor]] = {}
        self._master_weights: dict[int, Tensor] = {}
        self._step_count = 0
        self._aux_state: dict = {}
        # fused multi-tensor path (optimizer/flat.py): dtype buckets of
        # flat param/grad/moment buffers, built lazily at first step()
        self._flat: list[_flat.FlatGroup] | None = None
        self._fused_off = False
        self._defuse_count = 0
        self._flat_created_log: list | None = None  # StepGuard hook
        # 0-d device scalar holding the current LR: under jit capture it is
        # threaded as an input (synced from the scheduler host-side before
        # each compiled invocation), so LR changes don't retrigger tracing.
        # Created here, not lazily — it must pre-exist any capture so the
        # tracker classifies it as an input rather than a temporary.
        self._lr_var = Tensor(jnp.float32(self.get_lr()))
        self._publish_state_bytes()

    # --- lr -------------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate()
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "cannot set_lr when the learning rate is a scheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # --- accumulators (state lives in Tensors so jit capture threads it
    # through the compiled step as inputs/outputs) ------------------------
    def _acc(self, name, p, init=None, dtype=None, scalar=False):
        """``p``'s accumulator ``name``, made on first use: at ``p``'s
        shape, or with ``scalar`` ONE float32 number (0-d, like
        ``_lr_var``) for state that is the same in every element."""
        store = self._accumulators.setdefault(name, {})
        pid = id(p)
        if pid not in store:
            if scalar:
                store[pid] = Tensor(jnp.float32(init or 0.0))
            else:
                v = p._read()
                dt = dtype or (jnp.float32 if self._use_master(p)
                               else v.dtype)
                store[pid] = Tensor(jnp.zeros(v.shape, dt) if init is None
                                    else jnp.full(v.shape, init, dt))
        return store[pid]._read()

    def _set_acc(self, name, p, val):
        self._accumulators[name][id(p)]._write(val)

    def _use_master(self, p):
        return self._multi_precision and p._read().dtype in (
            jnp.bfloat16, jnp.float16)

    def _get_master(self, p):
        pid = id(p)
        if pid not in self._master_weights:
            self._master_weights[pid] = Tensor(
                p._read().astype(jnp.float32))
        return self._master_weights[pid]._read()

    def state_bytes(self):
        """Bytes of step state by part, reckoned on the host from shapes
        (a bucket's padding is not counted): the parameters, their
        float32 masters, the accumulators at a parameter's shape and the
        0-d ones."""
        def nbytes(ts):
            return sum(t.size * t.dtype.itemsize for t in ts)
        accs = [t for store in self._accumulators.values()
                for t in store.values()]
        accs += [t for grp in (self._flat or ())
                 for t in (grp.b1p, grp.b2p) if t is not None]
        return {"param": nbytes(self._parameters),
                "master": nbytes(self._master_weights.values()),
                "moments": nbytes(t for t in accs if t.ndim),
                "scalars": nbytes(t for t in accs if not t.ndim)}

    def _publish_state_bytes(self):
        """Point the ``optimizer.state_bytes`` gauges at this optimizer,
        the newest. Read at snapshot time only, so whichever path makes
        the state (``_acc``, ``_get_master``, the bucket build, a loaded
        checkpoint) is counted, and through a weak reference: the
        registry outlives the optimizer."""
        from ..observability import metrics
        ref = weakref.ref(self)
        for part in ("param", "master", "moments", "scalars"):
            metrics.registry().gauge(
                "optimizer.state_bytes",
                "bytes of step state of the newest optimizer",
                labels={"part": part}
            ).set_function(lambda part=part: ref().state_bytes()[part])

    # --- step -----------------------------------------------------------
    def _collect(self):
        pairs = []
        for p in self._parameters:
            if not getattr(p, "trainable", True) or p.stop_gradient:
                continue
            if p.grad is None:
                continue
            pairs.append((p, p.grad))
        return pairs

    def _apply_decay_to_grad(self, p, g32):
        """L2 regularization folded into the gradient (reference
        regularizer behavior — NOT decoupled adamw decay)."""
        reg = getattr(p, "regularizer", None) or self._regularization
        if isinstance(reg, L2Decay) and reg.coeff:
            master = (self._get_master(p) if self._use_master(p)
                      else p._read().astype(jnp.float32))
            return g32 + reg.coeff * master
        if isinstance(reg, L1Decay) and reg.coeff:
            master = (self._get_master(p) if self._use_master(p)
                      else p._read().astype(jnp.float32))
            return g32 + reg.coeff * jnp.sign(master)
        return g32

    @property
    def lr_var(self):
        """The captured LR scalar the compiled step reads — pass it as a
        ``jit.WindowRunner`` ``per_step`` tensor to feed a different LR
        to every step of a scanned window."""
        return self._lr_var

    def lr_window(self, length: int):
        """The next ``length`` scheduler LR values (current value first)
        as a float32 [length] array for a WindowRunner per-step slot,
        ADVANCING the scheduler by ``length`` steps — the window analog
        of calling ``scheduler.step()`` once per batch. With a fixed
        float LR the array is constant.

        The advance happens NOW, not when the window runs: if the
        subsequent ``run`` fails or is skipped, restore the scheduler
        from a prior ``state_dict()`` snapshot before retrying, or the
        schedule lands ``length`` steps ahead of the applied steps."""
        import numpy as np
        from .lr import LRScheduler
        sched = self._learning_rate
        if not isinstance(sched, LRScheduler):
            return np.full((length,), float(self._learning_rate),
                           np.float32)
        vals = []
        for _ in range(length):
            vals.append(float(sched()))
            sched.step()
        return np.asarray(vals, np.float32)

    def _live_lr(self):
        """Current LR as a traceable value. Under capture, reads the
        persistent lr scalar (a real program input) and registers a host-side
        sync so the scheduler's value is fed in before every invocation."""
        from ..core import tensor as _tm
        tr = _tm._tracker
        if tr is None:
            return self.get_lr()
        tr.add_host_sync(
            lambda: self._lr_var._write(jnp.float32(self.get_lr())))
        return self._lr_var._read()

    def step(self):
        with _scope.phase("optimizer"):
            self._step()

    def _step(self):
        self._step_count += 1
        pairs = self._collect()
        # step telemetry (ISSUE 8): eager-only wall time + fused bucket
        # dispatch count into the default observability registry. Under
        # jit capture the whole update is traced into the step program
        # — host timing there measures trace time, so skip it.
        from ..observability import metrics as _obs_metrics
        from ..observability.steptimer import note_optimizer_step
        import time as _time
        t0 = (_time.perf_counter()
              if _tm._tracker is None and _obs_metrics.enabled()
              else None)
        if self._fused_enabled():
            try:
                if self._fused_step(pairs):
                    if t0 is not None:
                        note_optimizer_step(
                            (_time.perf_counter() - t0) * 1e3,
                            fused_buckets=len(self._flat or ()))
                    return
            except _flat.FlatMismatch as e:
                self._defuse(str(e))
        elif self._flat is not None:
            # eligibility changed after fused steps ran (flag flipped,
            # clip swapped): fold bucket state — notably the per-bucket
            # beta-pow scalars — back into per-param accumulators before
            # the per-param path lazily re-creates them at 1.0
            self._defuse("fused path disabled", count=False)
        if self._grad_clip is not None:
            with _scope.phase("clip"):
                pairs = self._grad_clip(pairs)
        self._apply_pairs(pairs, self._live_lr())
        if t0 is not None:
            note_optimizer_step((_time.perf_counter() - t0) * 1e3)

    def _apply_pairs(self, pairs, lr):
        """The per-param update loop (grads already clipped)."""
        for p, g in pairs:
            lr_p = lr * p.optimize_attr.get("learning_rate", 1.0) \
                if hasattr(p, "optimize_attr") else lr
            g32 = g._read().astype(jnp.float32)
            g32 = self._apply_decay_to_grad(p, g32)
            if self._use_master(p):
                master = self._get_master(p)
                new_master = self._update(p, master, g32, lr_p)
                self._master_weights[id(p)]._write(new_master)
                p._write(new_master.astype(p._read().dtype))
            else:
                v = p._read()
                new_v = self._update(p, v.astype(jnp.float32), g32, lr_p)
                p._write(new_v.astype(v.dtype))

    # --- fused multi-tensor path (flat dtype buckets) --------------------
    def _fused_kind(self):
        """Fused-kernel kind for this optimizer, or None when the
        per-param path must run (subclasses override)."""
        return None

    _FUSED_MOMENTS = {"sgd": (), "momentum": ("velocity",),
                      "adam": ("moment1", "moment2"),
                      "adamw": ("moment1", "moment2")}

    def _fused_enabled(self):
        if self._fused_off or not _state.get_flag("fused_opt"):
            return False
        if self._fused_kind() is None:
            return False
        gc = self._grad_clip
        if gc is not None and not isinstance(gc, ClipGradByGlobalNorm):
            return False
        return True

    @staticmethod
    def _fusable_param(p, v, clip_active):
        if isinstance(v, (jax.core.Tracer, jax.ShapeDtypeStruct)) or \
                not hasattr(v, "dtype"):
            return False  # lazy / abstract (aot) values
        if not jnp.issubdtype(v.dtype, jnp.floating):
            return False
        if p._dist is not None:
            return False
        sh = getattr(v, "sharding", None)
        if sh is not None and len(getattr(sh, "device_set", ())) > 1 \
                and not sh.is_fully_replicated:
            return False  # keep sharded state sharded (fleet/mp)
        if hasattr(p, "optimize_attr") and \
                p.optimize_attr.get("learning_rate", 1.0) != 1.0:
            return False
        if getattr(p, "regularizer", None) is not None:
            return False
        if clip_active and getattr(p, "need_clip", True) is False:
            return False
        return True

    def _build_flat(self, pairs):
        """Group fusable params into dtype buckets and build the flat
        stores. Returns the group list or None (structural no-fuse).
        Every validation runs BEFORE any view is bound, so a no-fuse
        return leaves the optimizer's tensors untouched."""
        kind = self._fused_kind()
        clip_active = isinstance(self._grad_clip, ClipGradByGlobalNorm)
        by_dtype: dict = {}
        for p, _g in pairs:
            v = p._read()
            if not self._fusable_param(p, v, clip_active):
                continue
            dt = jnp.dtype(v.dtype)
            if dt != jnp.float32 and self._FUSED_MOMENTS[kind] and not (
                    self._multi_precision and
                    dt in (jnp.bfloat16, jnp.float16)):
                # the flat moment stores are f32 but the per-param path
                # keeps accumulators in the param dtype when no master
                # weight applies — fusing would break bitwise parity
                # (and fuse-or-not would depend on accumulator history)
                continue
            by_dtype.setdefault(dt, []).append((p, v))
        # A Mosaic kernel cannot sit in a program that XLA partitions
        # over a mesh ("Mosaic kernels cannot be automatically
        # partitioned"), and with any mesh-placed param the step is
        # such a program: the buckets of its unplaced params then take
        # the jnp update (what every bucket takes off the TPU).
        def on_mesh(p):
            sh = getattr(p._read(), "sharding", None)
            return p._dist is not None or (
                sh is not None and len(sh.device_set) > 1)

        self._flat_impl = "jnp" if any(
            on_mesh(p) for p, _g in pairs) else None
        # ---- validation pass (no mutation) ----
        betas = {}
        for dt, pv in by_dtype.items():
            members = [p for p, _ in pv]
            if kind in ("adam", "adamw"):
                got = self._uniform_beta_pows(members)
                if got is None:
                    return None
                betas[dt] = got
            if self._multi_precision and dt in (jnp.bfloat16, jnp.float16):
                for p, v in pv:
                    t = self._master_weights.get(id(p))
                    if t is None:
                        continue
                    tv = t._read()
                    if tv.dtype != jnp.float32 or \
                            tuple(tv.shape) != tuple(v.shape):
                        return None
            for name in self._FUSED_MOMENTS[kind]:
                store = self._accumulators.get(name, {})
                for p, v in pv:
                    t = store.get(id(p))
                    if t is None:
                        continue
                    tv = t._read()
                    if tv.dtype != jnp.float32 or \
                            tuple(tv.shape) != tuple(v.shape):
                        return None
        # ---- build pass ----
        groups = []
        log = self._flat_created_log
        for dt, pv in by_dtype.items():
            members = [p for p, _ in pv]
            values = [v for _, v in pv]
            use_master = self._multi_precision and dt in (
                jnp.bfloat16, jnp.float16)
            grp = _flat.FlatGroup(members, values, use_master)
            # beta powers collapse to one scalar per bucket; a prior
            # per-param history must be uniform for that to be exact
            b1v, b2v = betas.get(dt, (1.0, 1.0))
            pf = grp.flatten(values)
            grp.param_store = _flat.FlatStore(grp, "param", pf)
            if log is not None:
                log.append((grp.param_store.storage, pf))
            for i, p in enumerate(members):
                grp.param_store.bind(i, p)
            if use_master:
                if any(id(p) in self._master_weights for p in members):
                    mvals = []
                    for p, v in pv:
                        t = self._master_weights.get(id(p))
                        mvals.append(v.astype(jnp.float32) if t is None
                                     else t._read())
                    mf = grp.flatten(mvals, jnp.float32)
                else:
                    mf = pf.astype(jnp.float32)
                grp.master_store = _flat.FlatStore(grp, "master", mf)
                if log is not None:
                    log.append((grp.master_store.storage, mf))
                st = grp.master_store
                for i, p in enumerate(members):
                    t = self._master_weights.get(id(p))
                    if t is None:
                        t = Tensor(st._slice(mf, i))
                        self._master_weights[id(p)] = t
                    st.bind(i, t)
            for name in self._FUSED_MOMENTS[kind]:
                store = self._accumulators.setdefault(name, {})
                avals = []
                for p, v in pv:
                    t = store.get(id(p))
                    avals.append(jnp.zeros(v.shape, jnp.float32)
                                 if t is None else t._read())
                af = grp.flatten(avals, jnp.float32)
                st = _flat.FlatStore(grp, "moment", af)
                grp.moment_stores[name] = st
                if log is not None:
                    log.append((st.storage, af))
                for i, p in enumerate(members):
                    t = store.get(id(p))
                    if t is None:
                        t = Tensor(avals[i])
                        store[id(p)] = t
                    st.bind(i, t)
            if kind in ("adam", "adamw"):
                grp.b1p = Tensor(jnp.float32(b1v))
                grp.b2p = Tensor(jnp.float32(b2v))
                # the bucket's pair now counts for its members: their
                # own would go stale (a defuse hands the bucket's back)
                for name in self._SCALAR_ACCS:
                    store = self._accumulators.get(name, {})
                    for p in members:
                        store.pop(id(p), None)
                if log is not None:
                    log.append((grp.b1p, grp.b1p._read()))
                    log.append((grp.b2p, grp.b2p._read()))
            groups.append(grp)
        return groups or None

    def _uniform_beta_pows(self, members):
        """(b1, b2) when every member's saved beta-pow history agrees
        (the normal case: all params step together); None when mixed."""
        out = []
        for name in self._SCALAR_ACCS:
            store = self._accumulators.get(name, {})
            ts = [store.get(id(p)) for p in members]
            if all(t is None for t in ts):
                out.append(1.0)
                continue
            if any(t is None for t in ts):
                return None
            a = np.asarray(jax.device_get([t._read() for t in ts]))
            if not np.all(a == a[0]):
                return None
            out.append(float(a[0]))
        return out[0], out[1]

    def _make_spec(self, grp, has_clip):
        from ..ops.pallas.fused_optimizer import UpdateSpec
        kind = self._fused_kind()
        reg = self._regularization
        reg_kind, reg_coeff = None, 0.0
        if isinstance(reg, L2Decay) and reg.coeff:
            reg_kind, reg_coeff = "l2", reg.coeff
        elif isinstance(reg, L1Decay) and reg.coeff:
            reg_kind, reg_coeff = "l1", reg.coeff
        return UpdateSpec(
            kind=kind, beta1=getattr(self, "_beta1", 0.9),
            beta2=getattr(self, "_beta2", 0.999),
            eps=getattr(self, "_epsilon", 1e-8),
            momentum=getattr(self, "_momentum", 0.0),
            nesterov=getattr(self, "_nesterov", False),
            rescale=getattr(self, "_rescale", 1.0),
            decay=(self._coeff if kind == "adamw" else 0.0),
            reg=reg_kind, reg_coeff=reg_coeff,
            use_master=grp.use_master, has_clip=has_clip)

    def _gather_grads(self, grp, gmap):
        """Member grads -> the group's flat grad buffer (ONE concat),
        binding the grad tensors as views of it."""
        st = grp.grad_store
        gts = [gmap[id(p)] for p in grp.params]
        # the short-circuit (flat buffer already authoritative) is an
        # EAGER-only optimization: under capture the gather must always
        # run — discovery has to read the member grads so replay (whose
        # host flags are frozen post-discovery and which always takes
        # the gather branch) sees the same reads, and skipping it would
        # bake a program that ignores in-step grad accumulation
        if st is not None and not st._dirty and _tm._tracker is None \
                and all(st.owns(g, i) for i, g in enumerate(gts)):
            return
        vals = [g._read() for g in gts]
        # one bucket can hold grads of two dtypes: under AMP O2 an f32
        # norm weight whose block ran under recompute gets a bf16 grad
        # (the whole block is one op, cast at its boundary) while the
        # final norm's stays f32 — widen to the common dtype (exact)
        dt = jnp.result_type(*{v.dtype for v in vals})
        flat = grp.flatten(
            [v if v.dtype == dt else v.astype(dt) for v in vals], dt)
        if st is None:
            st = grp.grad_store = _flat.FlatStore(grp, "grad", flat)
        else:
            st.set_flat(flat)
        if _flat._replaying():
            # replay re-executes with temporary tracer grads: only the
            # value flow above is real, bindings must not mutate
            return
        anchor = st.storage._data
        concrete = _flat._concrete(anchor)
        for i, g in enumerate(gts):
            if not st.owns(g, i):
                st.bind(i, g)
            else:
                st.local[i] = False
            g._flat_src = anchor if concrete else None
        st._dirty = False

    def _fused_step(self, pairs):
        from ..ops.pallas import fused_optimizer as fo
        if not pairs:
            return False  # nothing to do; keep buckets/eligibility intact
        fl = self._flat
        if fl is None:
            fl = self._build_flat(pairs)
            if fl is None:
                self._fused_off = True  # structural: stop probing
                return False
            self._flat = fl
        gmap = {id(p): g for p, g in pairs}
        clip_active = isinstance(self._grad_clip, ClipGradByGlobalNorm)
        for grp in fl:
            for i, p in enumerate(grp.params):
                if id(p) not in gmap:
                    raise _flat.FlatMismatch(
                        "bucketed parameter has no gradient this step")
                if not grp.param_store.owns(p, i):
                    raise _flat.FlatMismatch(
                        "parameter re-bound outside its bucket")
                if getattr(p, "regularizer", None) is not None or \
                        (hasattr(p, "optimize_attr") and
                         p.optimize_attr.get("learning_rate", 1.0) != 1.0) \
                        or (clip_active and
                            getattr(p, "need_clip", True) is False):
                    raise _flat.FlatMismatch(
                        "per-param attribute changed after bucket build")
        bucketed = set()
        for grp in fl:
            bucketed.update(grp.pids)
        leftover = [(p, g) for p, g in pairs if id(p) not in bucketed]
        # fold any local view overrides (per-param fallback steps, user
        # writes) back into the flat buffers, then gather grads
        for grp in fl:
            for st in grp.stores():
                st.sync()
            self._gather_grads(grp, gmap)
        lr = self._live_lr()
        clip_scale = None
        with _scope.phase("clip"):
            if clip_active:
                sq = [jnp.sum(jnp.square(
                    grp.grad_store.storage._read().astype(jnp.float32)))
                    for grp in fl]
                for p, g in leftover:
                    if g is None or getattr(p, "need_clip", True) is False:
                        continue
                    sq.append(jnp.sum(jnp.square(
                        g._read().astype(jnp.float32))))
                if sq:
                    clip_scale = self._grad_clip._flat_scale(sq)
        if clip_scale is not None and leftover:
            leftover = ClipGradByGlobalNorm._apply_scale(leftover,
                                                         clip_scale)
        for grp in fl:
            spec = self._make_spec(grp, clip_scale is not None)
            kw = {}
            names = self._FUSED_MOMENTS[spec.kind]
            if names:
                kw["m"] = grp.moment_stores[names[0]].flat_value()
            if len(names) > 1:
                kw["v"] = grp.moment_stores[names[1]].flat_value()
            if spec.use_master:
                kw["master"] = grp.master_store.flat_value()
            if grp.b1p is not None:
                kw["b1p"] = grp.b1p._read()
                kw["b2p"] = grp.b2p._read()
            new_w, new_master, nm, nv, nb1, nb2 = fo.fused_update(
                spec, w=grp.param_store.flat_value(),
                g=grp.grad_store.storage._read(), lr=lr,
                clip_scale=clip_scale, impl=self._flat_impl, **kw)
            grp.param_store.set_flat(new_w)
            if new_master is not None:
                grp.master_store.set_flat(new_master)
            if nm is not None:
                grp.moment_stores[names[0]].set_flat(nm)
            if nv is not None:
                grp.moment_stores[names[1]].set_flat(nv)
            if nb1 is not None:
                grp.b1p._write(nb1)
                grp.b2p._write(nb2)
        if leftover:
            self._apply_pairs(leftover, lr)
        return True

    def _defuse(self, reason, warn=True, count=True):
        """Dissolve the flat buckets back into per-param tensors."""
        fl = self._flat
        if fl is None:
            return
        if _tm._tracker is not None:
            raise _flat.FlatMismatch(
                f"flat-bucket defuse required under jit capture ({reason})"
                " — defuse eagerly before capturing the step")
        for grp in fl:
            if grp.b1p is not None:
                for p in grp.params:
                    for name, t in zip(self._SCALAR_ACCS,
                                       (grp.b1p, grp.b2p)):
                        self._accumulators.setdefault(name, {})[id(p)] = \
                            Tensor(t._read())
            for st in grp.stores():
                st.unbind_all()
            if grp.grad_store is not None:
                grp.grad_store.unbind_all()
        self._flat = None
        if count:
            self._defuse_count += 1
            if self._defuse_count >= 2:
                self._fused_off = True
        if warn:
            warnings.warn(
                f"fused optimizer path defused: {reason} "
                f"(per-param fallback)")

    def _flat_unscale(self, inv):
        """Bucketed unscale + inf-check for ``amp.GradScaler``: one
        multiply and one isfinite reduction per flat bucket instead of
        per-param chains. Returns (found_inf, handled param ids)."""
        fl = self._flat
        if not fl:
            return False, set()
        gmap = {id(p): g for p, g in self._collect()}
        found = False
        handled: set[int] = set()
        for grp in fl:
            if any(id(p) not in gmap for p in grp.params):
                continue
            try:
                self._gather_grads(grp, gmap)
            except _flat.FlatMismatch:
                continue
            g32 = grp.grad_store.storage._read().astype(jnp.float32) * inv
            if not bool(jnp.all(jnp.isfinite(g32))):
                found = True
            grp.grad_store.set_flat(g32)
            handled.update(grp.pids)
        return found, handled

    def _fused_guard_slots(self):
        """Every flat storage the fused update writes — the slots
        ``resilience.StepGuard`` snapshots/blends instead of the
        per-param views (O(buckets) selects, not O(params))."""
        out = []
        for grp in (self._flat or ()):
            for st in grp.stores():
                st.sync()
                out.append(st.storage)
            if grp.b1p is not None:
                out.extend((grp.b1p, grp.b2p))
        return out

    minimize = None  # set below

    def _update(self, p, w, g, lr):
        raise NotImplementedError

    def clear_grad(self, set_to_zero=False):
        with _scope.phase("clear_grad"):
            self._clear_grad(set_to_zero)

    def _clear_grad(self, set_to_zero):
        # NOTE: the reference defaults set_to_zero=True (zero in place);
        # we default to dropping the buffer — zeroing is opt-in for
        # jit-captured gradient accumulation (hapi accumulate_grad_batches).
        handled: set[int] = set()
        if set_to_zero and self._flat is not None:
            # fused path: ONE zeros op per flat grad bucket; the
            # per-param grad views observe the zeros lazily
            for grp in self._flat:
                st = grp.grad_store
                if st is None:
                    continue
                if any(p._grad is None or not st.owns(p._grad, i)
                       for i, p in enumerate(grp.params)):
                    continue  # partially re-bound: per-param fallback
                st.fill_zeros()
                for p in grp.params:
                    p._grad._node = None
                    handled.add(id(p))
        for p in self._parameters:
            if id(p) in handled:
                continue
            p.clear_grad(set_to_zero=set_to_zero)

    clear_gradients = clear_grad

    # --- state dict -----------------------------------------------------
    def state_dict(self):
        sd = {}
        names = {id(p): (p.name or f"param_{i}")
                 for i, p in enumerate(self._parameters)}
        for acc_name, store in self._accumulators.items():
            for pid, val in store.items():
                if pid in names:
                    sd[f"{names[pid]}.{acc_name}"] = Tensor(val._read())
        for pid, val in self._master_weights.items():
            if pid in names:
                sd[f"{names[pid]}.master_weight"] = Tensor(val._read())
        # fused buckets keep ONE beta-pow pair per bucket; emit it per
        # param, 0-d as the per-param path keeps its own, so a checkpoint
        # reads the same from either path
        for grp in (self._flat or ()):
            if grp.b1p is None:
                continue
            for p in grp.params:
                nm = names.get(id(p))
                if nm is None:
                    continue
                for name, t in zip(self._SCALAR_ACCS, (grp.b1p, grp.b2p)):
                    sd[f"{nm}.{name}"] = Tensor(t._read())
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        sd["@step"] = self._step_count
        return sd

    def set_state_dict(self, sd):
        if self._flat is not None:
            # dissolve the buckets first: loading replaces the per-param
            # accumulator tensors wholesale; the buckets rebuild from the
            # loaded values at the next step()
            self._defuse("set_state_dict", warn=False, count=False)
        names = {(p.name or f"param_{i}"): p
                 for i, p in enumerate(self._parameters)}
        self._step_count = int(sd.get("@step", 0))
        if "LR_Scheduler" in sd and isinstance(self._learning_rate,
                                               LRScheduler):
            self._learning_rate.set_state_dict(sd["LR_Scheduler"])
        for key, val in sd.items():
            if key in ("LR_Scheduler", "@step"):
                continue
            pname, acc = key.rsplit(".", 1)
            p = names.get(pname)
            if p is None:
                continue
            arr = val._read() if isinstance(val, Tensor) else \
                jnp.asarray(np.asarray(val))
            if acc == "master_weight":
                self._master_weights[id(p)] = Tensor(arr)
                continue
            if acc in self._SCALAR_ACCS:
                arr = _one_number(key, arr)
            self._accumulators.setdefault(acc, {})[id(p)] = Tensor(arr)

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """Apply the update from gradients already on the parameters.

        Matches the reference dygraph semantics (``optimizer.py`` minimize
        collects existing ``p.grad`` pairs; it does NOT re-run autodiff), so
        the canonical ``loss.backward(); opt.minimize(loss)`` idiom applies
        each gradient exactly once.
        """
        self.step()
        return None, None


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)

    def _update(self, p, w, g, lr):
        return w - lr * g

    def _fused_kind(self):
        return "sgd"


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, rescale_grad=1.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov
        self._rescale = rescale_grad

    def _update(self, p, w, g, lr):
        if self._rescale != 1.0:
            g = g * self._rescale
        vel = self._acc("velocity", p)
        vel = self._momentum * vel + g
        self._set_acc("velocity", p, vel)
        if self._nesterov:
            return w - lr * (g + self._momentum * vel)
        return w - lr * vel

    def _fused_kind(self):
        return "momentum"


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, amsgrad=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._amsgrad = amsgrad

    def _fused_kind(self):
        return None if self._amsgrad else "adam"

    def _beta_pows(self, p):
        b1p = self._acc("beta1_pow", p, init=1.0, scalar=True)
        b2p = self._acc("beta2_pow", p, init=1.0, scalar=True)
        b1p = b1p * self._beta1
        b2p = b2p * self._beta2
        self._set_acc("beta1_pow", p, b1p)
        self._set_acc("beta2_pow", p, b2p)
        return b1p, b2p

    def _update(self, p, w, g, lr):
        m = self._acc("moment1", p, dtype=jnp.float32)
        v = self._acc("moment2", p, dtype=jnp.float32)
        b1p, b2p = self._beta_pows(p)
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * jnp.square(g)
        self._set_acc("moment1", p, m)
        self._set_acc("moment2", p, v)
        m_hat = m / (1 - b1p)
        if self._amsgrad:
            vmax = self._acc("moment2_max", p, dtype=jnp.float32)
            vmax = jnp.maximum(vmax, v)
            self._set_acc("moment2_max", p, vmax)
            v_hat = vmax / (1 - b2p)
        else:
            v_hat = v / (1 - b2p)
        return w - lr * m_hat / (jnp.sqrt(v_hat) + self._epsilon)


class AdamW(Adam):
    """Decoupled weight decay (reference ``adamw.py``): decay applies to the
    weight directly, not through the gradient."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, amsgrad=False,
                 name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision,
                         amsgrad=amsgrad, name=name)
        self._coeff = float(weight_decay) if not isinstance(
            weight_decay, (L1Decay, L2Decay)) else weight_decay.coeff
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _fused_kind(self):
        if self._amsgrad or self._lr_ratio is not None or \
                self._apply_decay_param_fun is not None:
            return None
        return "adamw"

    def _update(self, p, w, g, lr):
        if self._lr_ratio is not None:
            lr = lr * self._lr_ratio(p)
        decay = self._coeff
        if self._apply_decay_param_fun is not None and \
                not self._apply_decay_param_fun(p.name):
            decay = 0.0
        if decay:
            w = w * (1.0 - lr * decay)
        return super()._update(p, w, g, lr)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _update(self, p, w, g, lr):
        m = self._acc("moment", p, dtype=jnp.float32)
        u = self._acc("inf_norm", p, dtype=jnp.float32)
        b1p = self._acc("beta1_pow", p, init=1.0, scalar=True)
        b1p = b1p * self._beta1
        self._set_acc("beta1_pow", p, b1p)
        m = self._beta1 * m + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * u, jnp.abs(g))
        self._set_acc("moment", p, m)
        self._set_acc("inf_norm", p, u)
        return w - lr / (1 - b1p) * m / (u + self._epsilon)


class Adagrad(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _update(self, p, w, g, lr):
        acc = self._acc("moment", p, init=self._init_acc, dtype=jnp.float32)
        acc = acc + jnp.square(g)
        self._set_acc("moment", p, acc)
        return w - lr * g / (jnp.sqrt(acc) + self._epsilon)


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._epsilon, self._rho = epsilon, rho

    def _update(self, p, w, g, lr):
        avg_sq = self._acc("avg_squared_grad", p, dtype=jnp.float32)
        avg_up = self._acc("avg_squared_update", p, dtype=jnp.float32)
        avg_sq = self._rho * avg_sq + (1 - self._rho) * jnp.square(g)
        delta = jnp.sqrt(avg_up + self._epsilon) / \
            jnp.sqrt(avg_sq + self._epsilon) * g
        avg_up = self._rho * avg_up + (1 - self._rho) * jnp.square(delta)
        self._set_acc("avg_squared_grad", p, avg_sq)
        self._set_acc("avg_squared_update", p, avg_up)
        return w - lr * delta


class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-6,
                 momentum=0.0, centered=False, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _update(self, p, w, g, lr):
        ms = self._acc("mean_square", p, dtype=jnp.float32)
        mom = self._acc("momentum", p, dtype=jnp.float32)
        ms = self._rho * ms + (1 - self._rho) * jnp.square(g)
        self._set_acc("mean_square", p, ms)
        if self._centered:
            mg = self._acc("mean_grad", p, dtype=jnp.float32)
            mg = self._rho * mg + (1 - self._rho) * g
            self._set_acc("mean_grad", p, mg)
            denom = jnp.sqrt(ms - jnp.square(mg) + self._epsilon)
        else:
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * mom + lr * g / denom
        self._set_acc("momentum", p, mom)
        return w - mom


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name,
                         multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_decay = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _update(self, p, w, g, lr):
        m = self._acc("moment1", p, dtype=jnp.float32)
        v = self._acc("moment2", p, dtype=jnp.float32)
        b1p = self._acc("beta1_pow", p, init=1.0, scalar=True)
        b2p = self._acc("beta2_pow", p, init=1.0, scalar=True)
        b1p, b2p = b1p * self._beta1, b2p * self._beta2
        self._set_acc("beta1_pow", p, b1p)
        self._set_acc("beta2_pow", p, b2p)
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * jnp.square(g)
        self._set_acc("moment1", p, m)
        self._set_acc("moment2", p, v)
        m_hat = m / (1 - b1p)
        v_hat = v / (1 - b2p)
        r = m_hat / (jnp.sqrt(v_hat) + self._epsilon)
        decay = self._lamb_decay
        if self._exclude_fn is not None and self._exclude_fn(p):
            decay = 0.0
        update = r + decay * w
        w_norm = jnp.linalg.norm(w)
        u_norm = jnp.linalg.norm(update)
        trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
        return w - lr * trust * update


class LBFGS(Optimizer):
    """Limited-memory BFGS with two-loop recursion and optional
    strong-Wolfe line search (reference ``python/paddle/optimizer/lbfgs.py``:
    LBFGS :120, ``_strong_wolfe`` :247). Full-batch optimizer:
    ``step(closure)`` re-evaluates the loss/gradient as the line search
    probes points — closure must zero grads, run backward, return loss."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9,
                 history_size=100, line_search_fn=None, parameters=None,
                 weight_decay=None, grad_clip=None, name=None, **kwargs):
        super().__init__(learning_rate=learning_rate, parameters=parameters,
                         weight_decay=weight_decay, grad_clip=grad_clip)
        if line_search_fn not in (None, "strong_wolfe"):
            raise ValueError("line_search_fn must be None or 'strong_wolfe'")
        self.max_iter = max_iter
        self.max_eval = max_eval or max_iter * 5 // 4
        self.tol_grad = tolerance_grad
        self.tol_change = tolerance_change
        self.history_size = history_size
        self.line_search_fn = line_search_fn
        self._s: list = []
        self._y: list = []
        self._prev_flat_grad = None

    # -- flat parameter/grad views (float32 working precision) ---------
    def _trainable(self):
        return [p for p in self._parameters
                if getattr(p, "trainable", True) and not p.stop_gradient]

    def _flat_params(self):
        return jnp.concatenate(
            [p._read().astype(jnp.float32).ravel()
             for p in self._trainable()])

    def _flat_grad(self):
        gs = []
        for p in self._trainable():
            g = p.grad
            gs.append(jnp.zeros(p._read().size, jnp.float32) if g is None
                      else g._read().astype(jnp.float32).ravel())
        return jnp.concatenate(gs)

    def _set_flat_params(self, flat):
        off = 0
        for p in self._trainable():
            v = p._read()
            n = v.size
            p._write(flat[off:off + n].reshape(v.shape).astype(v.dtype))
            off += n

    def _dir_deriv(self, flat_grad, d):
        return float(jnp.dot(flat_grad, d))

    def _eval(self, closure, x, t, d):
        self._set_flat_params(x + t * d)
        loss = float(closure())
        g = self._flat_grad()
        return loss, g

    def step(self, closure):
        import numpy as _np
        with_ls = self.line_search_fn == "strong_wolfe"
        lr = float(self.get_lr())
        loss = float(closure())
        flat_grad = self._flat_grad()
        evals = 1
        if float(jnp.abs(flat_grad).max()) <= self.tol_grad:
            return loss

        for it in range(self.max_iter):
            # two-loop recursion
            q = flat_grad
            alphas = []
            for s, y in zip(reversed(self._s), reversed(self._y)):
                rho = 1.0 / max(float(jnp.dot(y, s)), 1e-10)
                a = rho * float(jnp.dot(s, q))
                alphas.append((a, rho, s, y))
                q = q - a * y
            if self._y:
                y_last, s_last = self._y[-1], self._s[-1]
                gamma = float(jnp.dot(s_last, y_last)) / max(
                    float(jnp.dot(y_last, y_last)), 1e-10)
                q = q * gamma
            for a, rho, s, y in reversed(alphas):
                b = rho * float(jnp.dot(y, q))
                q = q + s * (a - b)
            d = -q
            gtd = self._dir_deriv(flat_grad, d)
            if gtd > -self.tol_change:
                break

            x0 = self._flat_params()
            t = lr if (self._s or it > 0) else min(
                1.0, 1.0 / max(float(jnp.abs(flat_grad).sum()), 1e-10)) * lr
            if with_ls:
                t, loss_new, grad_new, ls_evals = _strong_wolfe(
                    lambda tt: self._eval(closure, x0, tt, d), t, d,
                    loss, flat_grad, gtd)
                evals += ls_evals
            else:
                loss_new, grad_new = self._eval(closure, x0, t, d)
                evals += 1
            self._set_flat_params(x0 + t * d)

            s = t * d
            ygrad = grad_new - flat_grad
            if float(jnp.dot(s, ygrad)) > 1e-10:
                self._s.append(s)
                self._y.append(ygrad)
                if len(self._s) > self.history_size:
                    self._s.pop(0)
                    self._y.pop(0)
            if (abs(loss_new - loss) < self.tol_change
                    or float(jnp.abs(grad_new).max()) <= self.tol_grad
                    or evals >= self.max_eval):
                loss, flat_grad = loss_new, grad_new
                break
            loss, flat_grad = loss_new, grad_new
        self._prev_flat_grad = flat_grad
        return loss

    def _update(self, p, w, g, lr):  # pragma: no cover - step() overridden
        raise RuntimeError("LBFGS.step requires a closure")


def _strong_wolfe(evaluate, t, d, f0, g0, gtd0, c1=1e-4, c2=0.9,
                  max_ls=25):
    """Strong-Wolfe cubic line search (reference ``lbfgs.py:247``).
    ``evaluate(t)`` -> (loss, flat_grad) at x0 + t*d."""
    import jax.numpy as jnp

    def dd(g):
        return float(jnp.dot(g, d))

    f_prev, g_prev, t_prev = f0, g0, 0.0
    evals = 0
    bracket = None
    for _ in range(max_ls):
        f_new, g_new = evaluate(t)
        evals += 1
        if f_new > f0 + c1 * t * gtd0 or (evals > 1 and f_new >= f_prev):
            bracket = (t_prev, t, f_prev, f_new, g_prev, g_new)
            break
        if abs(dd(g_new)) <= -c2 * gtd0:
            return t, f_new, g_new, evals
        if dd(g_new) >= 0:
            bracket = (t, t_prev, f_new, f_prev, g_new, g_prev)
            break
        t_prev, f_prev, g_prev = t, f_new, g_new
        t = t * 2.0
    else:
        return t, f_new, g_new, evals

    lo, hi, f_lo, f_hi, g_lo, g_hi = bracket
    for _ in range(max_ls):
        t = 0.5 * (lo + hi)
        f_new, g_new = evaluate(t)
        evals += 1
        if f_new > f0 + c1 * t * gtd0 or f_new >= f_lo:
            hi, f_hi, g_hi = t, f_new, g_new
        else:
            if abs(dd(g_new)) <= -c2 * gtd0:
                return t, f_new, g_new, evals
            if dd(g_new) * (hi - lo) >= 0:
                hi, f_hi, g_hi = lo, f_lo, g_lo
            lo, f_lo, g_lo = t, f_new, g_new
        if abs(hi - lo) < 1e-9:
            break
    return t, f_new, g_new, evals
