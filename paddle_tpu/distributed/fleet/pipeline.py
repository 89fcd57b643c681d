"""Pipeline parallelism — SPMD GPipe over a ``pp`` mesh axis.

Capability analog of the reference's pipeline stack (SURVEY D15-D17):
``python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py``
(schedules, 1F1B :663), ``parallel_layers/pp_layers.py`` (PipelineLayer /
LayerDesc), ``pp_utils/p2p_communication.py`` (stage P2P). The reference
runs one process per stage and hand-schedules NCCL send/recv; here the
whole pipeline is ONE SPMD program:

- the repeated block stack's parameters are stacked into ``[L, ...]``
  arrays sharded ``Shard(0)`` over the ``pp`` axis — stage assignment IS
  the sharding;
- a ``jax.shard_map`` + ``lax.scan`` runs the classic fill-drain (GPipe)
  schedule: at tick ``t`` stage ``i`` computes microbatch ``t - i`` and
  hands its activation to stage ``i+1`` via ``lax.ppermute`` (ICI
  neighbor hop — the p2p_communication analog);
- backward is JAX's transpose of the scan: activations flow backward
  through reversed ppermutes, giving the mirrored drain-fill schedule
  without a hand-written 1F1B engine. ``jax.checkpoint`` on the per-layer
  body keeps the live set to O(microbatch) per stage.

Bubble fraction is the textbook ``(pp-1)/(M+pp-1)`` — raise
``num_microbatches`` to amortize, exactly as with the reference's GPipe
mode.

P2P/compute overlap (``pp_overlap_p2p`` flag, default on): every
ppermute send is issued as soon as its payload exists — the forward
activation hop before the same tick's output banking, the backward
cotangent hop before the O(params) leaf-grad accumulation — so XLA's
scheduler can run the ICI transfer under independent compute (the
reference's async ``p2p_communication`` sends). Pure reordering:
values are bitwise-identical with the flag off.

Three schedules, matching the reference's set (D15):

- ``forward()`` (default) — FThenB/GPipe via scan + transpose;
- ``forward()`` with ``interleave=v > 1`` — interleaved virtual pipeline
  (reference ``pipeline_parallel.py:912``): stages hold v round-robin
  chunks, microbatches make v ppermute laps, bubble time shrinks by v;
- ``train_batch()`` — fused 1F1B (reference ``:663``): forward and
  backward micro-steps interleaved in ONE program with an O(pp) residual
  ring instead of O(M) saved activations.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ...core import state
from ...core import tensor as tensor_mod
from ...core.dispatch import apply
from ...core.tensor import Tensor
from ...nn.layer import Layer


def functional_call(layer: Layer, param_vals: dict, *args):
    """Run ``layer.forward`` as a PURE function of ``param_vals``
    (name -> raw array), torch.func.functional_call-style.

    Used to trace a Layer's computation with externally-managed (stacked /
    sliced / traced) parameter values: the layer's parameter tensors are
    temporarily re-pointed at ``param_vals``, the tape and any jit-capture
    tracker are disabled (the caller owns differentiation — usually the
    dispatch funnel's ``jax.vjp`` around the enclosing composite op), and
    the original buffers are restored afterwards."""
    params = dict(layer.named_parameters())
    missing = set(params) - set(param_vals)
    if missing:
        raise ValueError(f"functional_call missing values for {missing}")
    originals = {n: p._data for n, p in params.items()}

    def wrap(a):
        if isinstance(a, (tuple, list)):
            return type(a)(wrap(e) for e in a)
        return a if isinstance(a, Tensor) else Tensor(a)

    def unwrap(o):
        if isinstance(o, (tuple, list)):
            return type(o)(unwrap(e) for e in o)
        return o._data if isinstance(o, Tensor) else o

    old_tracker = tensor_mod.set_tracker(None)
    old_grad = state.set_grad_enabled(False)
    try:
        for n, p in params.items():
            p._data = param_vals[n]
        out = layer(*[wrap(a) for a in args])
    finally:
        state.set_grad_enabled(old_grad)
        tensor_mod.set_tracker(old_tracker)
        for n, p in params.items():
            p._data = originals[n]
    return unwrap(out)


from ...core.meshutil import pvary as _pvary
from ...core.meshutil import shard_map as _shard_map


def _overlap_p2p() -> bool:
    """pp_overlap_p2p flag (core/state.py): ppermute sends issued before
    the independent work of the same tick so the transfer hides under
    compute. Read at trace time; pure reordering, bitwise-identical."""
    return bool(state.get_flag("pp_overlap_p2p"))


class PipelinedBlocks(Layer):
    """A stack of ``num_layers`` structurally-identical blocks executed as
    an SPMD pipeline (see module docstring). The per-leaf parameters are
    stored STACKED (``[L, *shape]``) so ``Shard(0)`` over the pp axis
    assigns ``L/pp`` consecutive layers to each stage — the analog of the
    reference PipelineLayer's segment allocation (``pp_layers.py``
    ``_segment_network``).

    ``block_factory()`` must build one block Layer; blocks may not carry
    buffers or active dropout (single-program pipelining threads only
    parameters; RNG-bearing blocks would constant-fold their keys).
    """

    def __init__(self, block_factory: Callable[[], Layer], num_layers: int,
                 mesh=None, pp_axis: str = "pp", num_microbatches: int = 1,
                 remat: bool = True, interleave: int = 1):
        super().__init__()
        self.num_layers = num_layers
        self.pp_axis = pp_axis
        self.num_microbatches = num_microbatches
        self.remat = remat
        self.interleave = int(interleave)
        self._mesh = None
        self.template = block_factory()
        if any(True for _ in self.template.named_buffers()):
            raise ValueError("PipelinedBlocks: blocks must be buffer-free "
                             "(running stats can't thread the pipeline)")
        if self.interleave > 1 and mesh is None:
            raise ValueError("interleave > 1 needs the mesh at construction "
                             "(chunk assignment depends on the pp size)")
        # storage order: identity for v=1; round-robin chunks for VPP so a
        # CONTIGUOUS Shard(0) gives stage i its v chunks (layer (c*pp+i)*Lc+k
        # at storage slot i*Lp + c*Lc + k) — the reference's interleaved
        # stage->layers map (pipeline_parallel.py:912 virtual pipeline)
        self.layer_order = np.arange(num_layers)
        if self.interleave > 1:
            pp = self._pp_size(mesh, pp_axis)
            v = self.interleave
            if num_layers % (v * pp):
                raise ValueError(f"num_layers {num_layers} not divisible by "
                                 f"interleave*pp = {v}*{pp}")
            lc = num_layers // (v * pp)
            self.layer_order = np.asarray(
                [(c * pp + i) * lc + k
                 for i in range(pp) for c in range(v) for k in range(lc)])
        # stack L independent initializations leaf-wise -> [L, *shape]
        inits = [self.template] + [block_factory()
                                   for _ in range(num_layers - 1)]
        self._names = [n for n, _ in self.template.named_parameters()]
        for n in self._names:
            leaves = [dict(b.named_parameters())[n]._read() for b in inits]
            leaves = [leaves[j] for j in self.layer_order]
            stacked = Tensor(jnp.stack(leaves, axis=0), stop_gradient=False)
            self.add_parameter(self._mangle(n), _as_param(stacked))
        if mesh is not None:
            self.shard(mesh, pp_axis)

    @staticmethod
    def _pp_size(mesh, pp_axis):
        jmesh = getattr(mesh, "jmesh", mesh)
        return dict(zip(jmesh.axis_names, jmesh.devices.shape))[pp_axis]

    def layer_values(self, name: str):
        """Per-layer values of a stacked leaf in ORIGINAL layer order
        (undoes the VPP storage permutation)."""
        vals = self.stacked_parameter(name)._read()
        inv = np.argsort(self.layer_order)
        return [vals[int(j)] for j in inv]

    @staticmethod
    def _mangle(name: str) -> str:
        return "stacked__" + name.replace(".", "__")

    def stacked_parameter(self, name: str):
        return self._parameters[self._mangle(name)]

    def shard(self, mesh, pp_axis: str = "pp", tp_axis=None,
              tp_rules=None):
        """Pin Shard(0) over ``pp_axis`` on every stacked leaf.

        ``tp_axis``/``tp_rules`` add Megatron TP *inside* the pipeline
        (the reference's pp x mp hybrid, ``topology.py`` +
        ``semi_auto_parallel_simple_net_dp_mp_pp.py``): ``tp_rules`` maps
        a parameter-name substring to the STACKED-array dim to shard over
        ``tp_axis`` (e.g. ``{"qkv.weight": 2, "proj.weight": 1}``). The
        pipeline's shard_map then leaves ``tp_axis`` to GSPMD
        (``axis_names`` excludes it), so XLA inserts the TP collectives
        inside each stage while ppermute rides the pp axis."""
        from ..auto_parallel.api import Replicate, Shard, shard_parameter
        self._mesh = mesh
        self.pp_axis = pp_axis
        if tp_axis is not None and tp_axis not in mesh.dim_names:
            raise ValueError(
                f"tp_axis {tp_axis!r} is not a mesh dim "
                f"{mesh.dim_names} — refusing to silently train "
                "replicated")
        if tp_rules and tp_axis is None:
            raise ValueError("tp_rules given without tp_axis")
        self._tp_axis = tp_axis
        dim = mesh.dim_names.index(pp_axis)
        for n in self._names:
            pl = [Replicate()] * mesh.ndim
            pl[dim] = Shard(0)
            if self._tp_axis and tp_rules:
                for pat, tdim in tp_rules.items():
                    if pat in n:
                        pl[mesh.dim_names.index(tp_axis)] = Shard(tdim)
                        break
            shard_parameter(self.stacked_parameter(n), mesh, pl)
        return self

    def _manual_axes(self, jmesh):
        """Mesh axes the pipeline shard_map handles manually — everything
        except the TP axis, which stays under GSPMD."""
        names = tuple(jmesh.axis_names)
        tp = getattr(self, "_tp_axis", None)
        return frozenset(n for n in names if n != tp)

    def _audit_impl(self, name, impl, args):
        """Whole-program audit (analysis/program.py) of a pipeline
        shard_map body: the ppermute ring + psum schedule is exactly
        what PDT22x reasons about. Once per (pipeline, schedule name),
        at the dispatch that first compiles it — compile-time only."""
        done = self.__dict__.setdefault("_pp_audit_done", set())
        if name in done:
            return
        done.add(name)
        from ... import analysis as _analysis
        from ...core.tensor import Tensor as _T
        vals = tuple(a._read() if isinstance(a, _T) else a for a in args)
        _analysis.audit_jitted(impl, vals, where=f"pipeline.{name}")

    # -- the schedules -------------------------------------------------
    def forward(self, x, batch_axes=None):
        if self._mesh is None:
            raise RuntimeError("call .shard(mesh, pp_axis) first")
        if self.interleave > 1:
            return self._forward_interleaved(x, batch_axes)
        mesh = self._mesh
        jmesh = getattr(mesh, "jmesh", mesh)
        pp = self._pp_size(mesh, self.pp_axis)
        M = self.num_microbatches
        L, ax = self.num_layers, self.pp_axis
        if L % pp:
            raise ValueError(f"num_layers {L} not divisible by pp {pp}")
        template, names = self.template, self._names
        remat = self.remat
        if isinstance(batch_axes, str):
            batch_tuple = (batch_axes,)
        else:
            batch_tuple = tuple(batch_axes or ())
        vary_axes = (ax,) + batch_tuple

        leaf_tensors = [self.stacked_parameter(n) for n in names]

        def impl(xv, *leaves):
            b = xv.shape[0]
            if b % M:
                raise ValueError(f"batch {b} not divisible by "
                                 f"num_microbatches {M}")
            xm = xv.reshape((M, b // M) + xv.shape[1:])

            def block_apply(h, layer_leaves):
                vals = dict(zip(names, layer_leaves))
                y = functional_call(template, vals, h)
                return y, None

            if remat:
                block_apply = jax.checkpoint(block_apply)

            def local(xloc, *lvs):
                i = lax.axis_index(ax)
                mb_shape = xloc.shape[1:]

                def tick(carry, t):
                    h_in, outputs = carry
                    inject = xloc[jnp.clip(t, 0, M - 1)]
                    h = jnp.where(i == 0, inject, h_in)
                    y, _ = lax.scan(block_apply, h, lvs)
                    ring = [(r, (r + 1) % pp) for r in range(pp)]
                    if _overlap_p2p():
                        # issue the neighbor send FIRST: the output
                        # banking below is independent of it, so the ICI
                        # transfer runs under that work instead of after
                        # it (the p2p/compute overlap of the reference's
                        # p2p_communication async sends). Values are
                        # bitwise-identical either way — only the
                        # schedule moves.
                        nxt = lax.ppermute(y, ax, ring)
                    m_out = t - (pp - 1)
                    idx = jnp.clip(m_out, 0, M - 1)
                    valid = (i == pp - 1) & (m_out >= 0)
                    cur = lax.dynamic_index_in_dim(outputs, idx, 0,
                                                   keepdims=False)
                    outputs = lax.dynamic_update_index_in_dim(
                        outputs, jnp.where(valid, y, cur), idx, 0)
                    if not _overlap_p2p():
                        nxt = lax.ppermute(y, ax, ring)
                    return (nxt, outputs), None

                h0 = jnp.zeros(mb_shape, xloc.dtype)
                out0 = jnp.zeros((M,) + mb_shape, xloc.dtype)
                h0, out0 = _pvary((h0, out0), vary_axes)
                (_, outputs), _ = lax.scan(tick, (h0, out0),
                                           jnp.arange(M + pp - 1))
                # results live on the last stage; replicate over pp
                outputs = lax.psum(
                    jnp.where(i == pp - 1, outputs, 0), ax)
                return outputs

            xspec = P(None, batch_axes, *([None] * (xv.ndim - 1)))
            lspec = tuple(P(ax) for _ in leaves)
            out = _shard_map(local, mesh=jmesh,
                             in_specs=(xspec,) + lspec,
                             out_specs=xspec,
                             axis_names=self._manual_axes(jmesh),
                             )(xm, *leaves)
            return out.reshape((b,) + xv.shape[1:])

        # host-side tracing span around the whole pipelined dispatch
        # (ISSUE 12): the ppermute hops themselves are in-program
        # (XLA-scheduled), so the span brackets what the host can see —
        # the dispatch that contains them, with the schedule knobs as
        # attrs.  Under jit capture this runs once, at trace time.
        # The collective watchdog (ISSUE 15, collective_timeout_ms
        # flag) arms the same bracket: a ppermute ring wedged behind a
        # dead stage raises PDT-E021 with stacks instead of hanging.
        from ...observability import tracing as _tracing
        from ...observability import watchdog as _watchdog
        with _tracing.span("pp.forward", stages=pp, microbatches=M,
                           overlap_p2p=_overlap_p2p()), \
                _watchdog.arm_collective("pp.forward", key=self.pp_axis):
            self._audit_impl("pipelined_blocks", impl,
                             (x, *leaf_tensors))
            return apply("pipelined_blocks", impl, x, *leaf_tensors)

    def _forward_interleaved(self, x, batch_axes=None):
        """Interleaved virtual pipeline (reference
        ``pipeline_parallel.py:912`` interleaved 1F1B's stage layout,
        ``pp_layers.py`` virtual-pipeline chunks): each stage holds
        ``v = interleave`` round-robin layer chunks and microbatches
        circulate the ppermute ring ``v`` laps. Per-tick work is a chunk
        (1/v of a stage), so the fill/drain bubble time shrinks by v —
        the VPP bubble equation (pp-1)/(vM) vs GPipe's (pp-1)/M."""
        mesh = self._mesh
        jmesh = getattr(mesh, "jmesh", mesh)
        pp = self._pp_size(mesh, self.pp_axis)
        v, M, ax = self.interleave, self.num_microbatches, self.pp_axis
        lc = self.num_layers // (v * pp)  # layers per chunk
        template, names, remat = self.template, self._names, self.remat
        batch_tuple = ((batch_axes,) if isinstance(batch_axes, str)
                       else tuple(batch_axes or ()))
        vary_axes = (ax,) + batch_tuple
        leaf_tensors = [self.stacked_parameter(n) for n in names]

        def impl(xv, *leaves):
            b = xv.shape[0]
            if b % M:
                raise ValueError(f"batch {b} not divisible by "
                                 f"num_microbatches {M}")
            xm = xv.reshape((M, b // M) + xv.shape[1:])

            def block_apply(h, layer_leaves):
                vals = dict(zip(names, layer_leaves))
                return functional_call(template, vals, h), None

            if remat:
                block_apply = jax.checkpoint(block_apply)

            def chunk_apply(h, lvs, c):
                sl = [lax.dynamic_slice_in_dim(lv, c * lc, lc, axis=0)
                      for lv in lvs]
                y, _ = lax.scan(block_apply, h, tuple(sl))
                return y

            def local(xloc, *lvs):
                i = lax.axis_index(ax)
                mb_shape = xloc.shape[1:]
                done = v * pp  # hop count meaning "finished / empty slot"

                def tick(carry, t):
                    h, hops, mbid, inj, outputs = carry
                    at0 = i == 0
                    finished = hops >= done
                    # stage 0: bank a finished microbatch, inject the next
                    rec = at0 & finished & (mbid >= 0)
                    oc = jnp.clip(mbid, 0, M - 1)
                    cur = lax.dynamic_index_in_dim(outputs, oc, 0,
                                                   keepdims=False)
                    outputs = lax.dynamic_update_index_in_dim(
                        outputs, jnp.where(rec, h, cur), oc, 0)
                    take = at0 & finished & (inj < M)
                    h = jnp.where(take, xloc[jnp.clip(inj, 0, M - 1)], h)
                    mbid = jnp.where(take, inj,
                                     jnp.where(finished, -1, mbid))
                    hops = jnp.where(take, 0, hops)
                    inj = inj + take.astype(inj.dtype)
                    # apply this stage's chunk for the current lap
                    active = hops < done
                    c = jnp.clip(hops // pp, 0, v - 1)
                    y = chunk_apply(h, lvs, c)
                    h = jnp.where(active, y, h)
                    hops = jnp.where(active, hops + 1, hops)
                    ring = [(r, (r + 1) % pp) for r in range(pp)]
                    h = lax.ppermute(h, ax, ring)
                    hops = lax.ppermute(hops, ax, ring)
                    mbid = lax.ppermute(mbid, ax, ring)
                    return (h, hops, mbid, inj, outputs), None

                h0 = jnp.zeros(mb_shape, xloc.dtype)
                out0 = jnp.zeros((M,) + mb_shape, xloc.dtype)
                carry0 = _pvary(
                    (h0, jnp.int32(done), jnp.int32(-1), jnp.int32(0),
                     out0), vary_axes)
                # last microbatch M-1 enters slot (M-1)%pp at tick
                # (M-1)%pp + ((M-1)//pp)*v*pp and is banked v*pp ticks
                # later — run exactly until then (v*M + pp only covers
                # M a multiple of pp)
                t_bank = ((M - 1) % pp) + ((M - 1) // pp) * v * pp + v * pp
                carry = lax.scan(tick, carry0,
                                 jnp.arange(t_bank + 1))[0]
                outputs = carry[4]
                return lax.psum(jnp.where(i == 0, outputs, 0), ax)

            xspec = P(None, batch_axes, *([None] * (xv.ndim - 1)))
            lspec = tuple(P(ax) for _ in leaves)
            out = _shard_map(local, mesh=jmesh,
                             in_specs=(xspec,) + lspec,
                             out_specs=xspec,
                             axis_names=self._manual_axes(jmesh),
                             )(xm, *leaves)
            return out.reshape((b,) + xv.shape[1:])

        self._audit_impl("pipelined_blocks_vpp", impl, (x, *leaf_tensors))
        return apply("pipelined_blocks_vpp", impl, x, *leaf_tensors)

    def train_batch(self, x, target, loss_fn, batch_axes=None,
                    post_params=None):
        """Fused 1F1B train step (reference ``pipeline_parallel.py:663``
        ``train_batch`` / ``forward_backward_pipeline``): ONE SPMD program
        runs forward and backward micro-steps interleaved, holding at most
        ``2*pp`` microbatch residuals per stage (the 1F1B memory property
        — vs O(M) for the scan-transpose GPipe path), recomputing each
        chunk's vjp from the saved chunk input (recompute policy).

        ``loss_fn(y, target_mb)`` (or ``loss_fn(y, target_mb,
        post_vals)`` with ``post_params``) -> scalar mean loss, run on the
        last stage. ``post_params`` lets a trailing trainable epilogue
        (final norm, tied LM head) live inside the schedule: their raw
        values are passed to ``loss_fn`` and their grads flow back like
        the stacked leaves'. Returns the scalar mean loss;
        ``loss.backward()`` flows grads into the stacked leaves, ``x``,
        and the post params through the recorded vjp, so optimizers work
        unchanged.

        Schedule: tick ``t`` runs forward of microbatch ``t - i`` and
        backward of microbatch ``t - (2pp - 1 - i)`` on stage ``i``;
        activations hop forward and cotangents hop backward one ppermute
        per tick. The last stage's loss-vjp is folded into the uniform
        per-tick vjp by differentiating ``where(is_last, loss, <y, g>)``,
        so every tick costs exactly one chunk fwd + one chunk vjp.
        """
        if self._mesh is None:
            raise RuntimeError("call .shard(mesh, pp_axis) first")
        if self.interleave > 1:
            raise NotImplementedError("train_batch schedules plain 1F1B; "
                                      "use interleave=1 (VPP forward is "
                                      "available via __call__)")
        mesh = self._mesh
        jmesh = getattr(mesh, "jmesh", mesh)
        pp = self._pp_size(mesh, self.pp_axis)
        M, ax = self.num_microbatches, self.pp_axis
        L = self.num_layers
        if L % pp:
            raise ValueError(f"num_layers {L} not divisible by pp {pp}")
        template, names = self.template, self._names
        batch_tuple = ((batch_axes,) if isinstance(batch_axes, str)
                       else tuple(batch_axes or ()))
        vary_axes = (ax,) + batch_tuple
        sizes = dict(zip(jmesh.axis_names, jmesh.devices.shape))
        dp_n = int(np.prod([sizes[a] for a in batch_tuple])) \
            if batch_tuple else 1
        leaf_tensors = [self.stacked_parameter(n) for n in names]
        post_params = list(post_params or [])
        n_leaves = len(leaf_tensors)

        def impl(xv, tgt, *leaves_and_post):
            leaves = leaves_and_post[:n_leaves]
            post_vals_in = leaves_and_post[n_leaves:]
            b = xv.shape[0]
            if b % M:
                raise ValueError(f"batch {b} not divisible by "
                                 f"num_microbatches {M}")
            xm = xv.reshape((M, b // M) + xv.shape[1:])
            tm = tgt.reshape((M, b // M) + tgt.shape[1:])
            seed = 1.0 / (M * dp_n)

            def run(xmv, tmv, *lvs_and_post):
                lvs_in = lvs_and_post[:n_leaves]
                post_in = lvs_and_post[n_leaves:]
                def block_apply(h, layer_leaves):
                    vals = dict(zip(names, layer_leaves))
                    return functional_call(template, vals, h), None

                def chunk_fwd(h, lvs):
                    y, _ = lax.scan(block_apply, h, lvs)
                    return y

                def local(xloc, tloc, *lvs_all):
                    lvs = lvs_all[:n_leaves]
                    post = lvs_all[n_leaves:]
                    i = lax.axis_index(ax)
                    is_last = i == pp - 1
                    mb_shape = xloc.shape[1:]
                    R = 2 * pp
                    fwd_ring = [(r, (r + 1) % pp) for r in range(pp)]
                    bwd_ring = [(r, (r - 1) % pp) for r in range(pp)]

                    def objective(h, lvs, pv, t_mb, g):
                        """where(is_last, seed*loss, <y, g>): its
                        (h, lvs, pv) vjp is the loss-vjp on the last
                        stage and the cotangent-g chunk vjp elsewhere."""
                        y = chunk_fwd(h, lvs)
                        loss = (loss_fn(y, t_mb, pv) if pv
                                else loss_fn(y, t_mb))
                        obj = jnp.where(is_last, loss * seed,
                                        jnp.vdot(y, g))
                        return obj, loss

                    def tick(carry, t):
                        (h_fwd, g_bwd, ring, dacc, dpacc, loss_acc,
                         dx) = carry
                        # ---- forward micro-step: mb u = t - i ----
                        u = t - i
                        uc = jnp.clip(u, 0, M - 1)
                        h_in = jnp.where(i == 0, xloc[uc], h_fwd)
                        # bank the chunk input; slot t%R frees before reuse
                        ring = lax.dynamic_update_index_in_dim(
                            ring, h_in, t % R, 0)
                        y = chunk_fwd(h_in, lvs)
                        h_next = lax.ppermute(y, ax, fwd_ring)
                        # ---- backward micro-step: mb m ----
                        m = t - (2 * pp - 1 - i)
                        bvalid = (m >= 0) & (m < M)
                        mc = jnp.clip(m, 0, M - 1)
                        slot = (t - (2 * pp - 1 - 2 * i)) % R
                        h_saved = lax.dynamic_index_in_dim(
                            ring, slot, 0, keepdims=False)
                        obj, vjp, loss = jax.vjp(
                            lambda hh, ll, pv: objective(
                                hh, ll, pv, tloc[mc], g_bwd),
                            h_saved, lvs, tuple(post), has_aux=True)
                        dh, dlvs, dpost = vjp(
                            _pvary(jnp.ones((), obj.dtype), vary_axes))
                        if _overlap_p2p():
                            # issue the cotangent send as soon as dh
                            # exists: the O(params) leaf-grad
                            # accumulation below is independent of it,
                            # so the backward ICI hop runs under that
                            # work (values bitwise-identical; schedule
                            # only)
                            g_next = lax.ppermute(
                                jnp.where(bvalid, dh,
                                          jnp.zeros_like(dh)),
                                ax, bwd_ring)
                        dacc = tuple(
                            da + jnp.where(bvalid, dl, 0)
                            for da, dl in zip(dacc, dlvs))
                        # dpost is auto-psummed over pp+dp (invarying
                        # inputs); mid stages contribute exact zeros, so
                        # gate by the LAST stage's mb validity at this
                        # tick (same value on every device)
                        m_last = t - pp
                        glast = (m_last >= 0) & (m_last < M)
                        dpacc = tuple(
                            da + jnp.where(glast, dp_, 0)
                            for da, dp_ in zip(dpacc, dpost))
                        loss_acc = loss_acc + jnp.where(
                            bvalid & is_last, loss, 0.0)
                        curx = lax.dynamic_index_in_dim(dx, mc, 0,
                                                        keepdims=False)
                        dx = lax.dynamic_update_index_in_dim(
                            dx, jnp.where(bvalid & (i == 0), dh, curx),
                            mc, 0)
                        if not _overlap_p2p():
                            g_next = lax.ppermute(
                                jnp.where(bvalid, dh,
                                          jnp.zeros_like(dh)),
                                ax, bwd_ring)
                        return (h_next, g_next, ring, dacc, dpacc,
                                loss_acc, dx), None

                    # dacc inherits pp-varying from the leaves and stays
                    # dp-INvarying: the vjp transpose auto-psums leaf
                    # cotangents over dp (invarying input x varying seed),
                    # so dl already carries the cross-dp sum
                    dacc0 = tuple(jnp.zeros_like(lv) for lv in lvs)
                    dpacc0 = tuple(jnp.zeros_like(pv) for pv in post)
                    h0, g0, ring0, loss0, dx0 = _pvary((
                        jnp.zeros(mb_shape, xloc.dtype),
                        jnp.zeros(mb_shape, xloc.dtype),
                        jnp.zeros((R,) + mb_shape, xloc.dtype),
                        jnp.zeros((), xloc.dtype),
                        jnp.zeros((M,) + mb_shape, xloc.dtype),
                    ), vary_axes)
                    carry0 = (h0, g0, ring0, dacc0, dpacc0, loss0, dx0)
                    carry, _ = lax.scan(tick, carry0,
                                        jnp.arange(M + 2 * pp - 1))
                    _, _, _, dacc, dpacc, loss_acc, dx = carry
                    # loss lives on the last stage; grads of x on stage 0
                    loss_out = lax.psum(
                        jnp.where(is_last, loss_acc, 0.0), ax)
                    dx = lax.psum(jnp.where(i == 0, dx, 0.0), ax)
                    if batch_tuple:
                        loss_out = lax.psum(loss_out, batch_tuple)
                    return (loss_out, dx) + tuple(dacc) + tuple(dpacc)

                xspec = P(None, batch_axes,
                          *([None] * (xm.ndim - 2)))
                tspec = P(None, batch_axes,
                          *([None] * (tm.ndim - 2)))
                lspec = tuple(P(ax) for _ in lvs_in)
                pspec = tuple(P() for _ in post_in)
                outs = _shard_map(
                    local, mesh=jmesh,
                    in_specs=(xspec, tspec) + lspec + pspec,
                    out_specs=(P(), xspec) + lspec + pspec)(
                        xmv, tmv, *lvs_in, *post_in)
                loss, dx = outs[0], outs[1]
                dls = outs[2:2 + n_leaves]
                dps = outs[2 + n_leaves:]
                return loss / (M * dp_n), dx, dls, dps

            @jax.custom_vjp
            def op(xmv, *rest):
                return run(xmv, tm, *rest)[0]

            def op_fwd(xmv, *rest):
                loss, dx, dls, dps = run(xmv, tm, *rest)
                return loss, (dx, dls, dps)

            def op_bwd(res, g):
                dx, dls, dps = res  # dx already has xm's shape
                return ((g * dx,) + tuple(g * dl for dl in dls)
                        + tuple(g * dp_ for dp_ in dps))

            op.defvjp(op_fwd, op_bwd)
            return op(xm, *leaves, *post_vals_in)

        # span over the 1F1B dispatch (forward+backward hops inside);
        # see the pp.forward note — hops are in-program, the span is
        # the host-observable bracket around them (the collective
        # watchdog arms the same bracket, ISSUE 15)
        from ...observability import tracing as _tracing
        from ...observability import watchdog as _watchdog
        with _tracing.span("pp.train_batch", stages=pp, microbatches=M,
                           overlap_p2p=_overlap_p2p()), \
                _watchdog.arm_collective("pp.train_batch",
                                         key=self.pp_axis):
            self._audit_impl("pipeline_1f1b", impl,
                             (x, target, *leaf_tensors, *post_params))
            return apply("pipeline_1f1b", impl, x, target,
                         *leaf_tensors, *post_params)


def _as_param(t: Tensor):
    from ...core.tensor import Parameter
    if isinstance(t, Parameter):
        return t
    return Parameter(t._read(), trainable=True)


class LayerDesc:
    """Reference ``pp_layers.py`` LayerDesc parity: a deferred layer
    constructor (so each pipeline instantiation builds fresh params)."""

    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build_layer(self) -> Layer:
        return self.layer_cls(*self.args, **self.kwargs)


class PipelineLayer(Layer):
    """Reference ``PipelineLayer`` parity for HOMOGENEOUS descs: every
    ``LayerDesc`` must build the same block structure (the transformer
    case pipeline parallelism exists for). Heterogeneous pre/post layers
    (embedding, head) belong OUTSIDE — run them unsharded around this
    stack, as ``GPTForCausalLMPipe`` does (reference keeps them in
    first/last stages; with GSPMD they simply stay on their own sharding).
    """

    def __init__(self, layers, num_stages=None, mesh=None, pp_axis="pp",
                 num_microbatches=1, remat=True, interleave=1):
        super().__init__()
        descs = list(layers)
        if not descs:
            raise ValueError("PipelineLayer needs at least one LayerDesc")
        if not all(isinstance(d, LayerDesc) for d in descs):
            raise TypeError("PipelineLayer(layers=...) takes LayerDesc "
                            "items (wrap eager layers in LayerDesc)")
        first = descs[0]
        if any(d.layer_cls is not first.layer_cls or d.args != first.args
               or d.kwargs != first.kwargs for d in descs[1:]):
            raise NotImplementedError(
                "SPMD pipelining requires structurally identical blocks; "
                "move heterogeneous prologue/epilogue layers outside the "
                "PipelineLayer")
        self.blocks = PipelinedBlocks(first.build_layer, len(descs),
                                      mesh=mesh, pp_axis=pp_axis,
                                      num_microbatches=num_microbatches,
                                      remat=remat, interleave=interleave)

    def forward(self, x, batch_axes=None):
        return self.blocks(x, batch_axes=batch_axes)

    def train_batch(self, x, target, loss_fn, batch_axes=None,
                    post_params=None):
        """Fused 1F1B step (see ``PipelinedBlocks.train_batch``)."""
        return self.blocks.train_batch(x, target, loss_fn,
                                       batch_axes=batch_axes,
                                       post_params=post_params)
