"""Mixture-of-Experts with expert parallelism (the ``ep`` mesh axis).

Capability analog of the reference MoE stack (SURVEY D18):
``python/paddle/incubate/distributed/models/moe/moe_layer.py`` (MoELayer),
``gate/{naive,switch,gshard}_gate.py``, and the
``global_scatter/global_gather`` dispatch collectives
(``paddle/distributed/utils/moe_utils.py``). The reference routes tokens
with explicit NCCL all-to-alls; here dispatch/combine are capacity-bucketed
einsums (the GShard formulation) over expert-stacked ``[E, ...]`` weights
sharded ``Shard(0)`` over the ``ep`` axis — XLA's partitioner emits the
all-to-alls when token shardings (dp) and expert shardings (ep) meet in
the dispatch einsum, and they ride ICI.

Top-k routing with renormalized combine weights, per-expert capacity
``C = ceil(k * N / E * capacity_factor)``, overflow tokens dropped
(GShard/Switch semantics), and the switch-style load-balance auxiliary
loss ``E * sum(importance * load)``.

``SparseMoEBlock`` beside it is the dropless formulation a chip of an
expert-parallel deployment runs: a router over ALL the experts, grouped
matrix products over the tokens routed to the experts held HERE.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ....core import scope as _scope
from ....core.autograd import no_grad
from ....core.dispatch import apply
from ....core.tensor import Parameter, Tensor
from ....nn.layer import Layer
from ....nn.layers import Linear


def _one_hot(idx, n, dtype=jnp.float32):
    return jax.nn.one_hot(idx, n, dtype=dtype)


def moe_dispatch_combine(gates, k, capacity):
    """Build dispatch/combine tensors from gate probabilities.

    gates: [N, E] softmax probabilities. Returns (dispatch [N, E, C] 0/1,
    combine [N, E, C] weights, aux_loss scalar). Slot 0 (top-1 choices)
    fills capacity first, then slot 1, matching the reference gshard gate's
    priority order."""
    n, e = gates.shape
    gval, gidx = jax.lax.top_k(gates, k)          # [N, k]
    gval = gval / jnp.maximum(gval.sum(-1, keepdims=True), 1e-9)

    counts = jnp.zeros((e,), jnp.float32)
    dispatch = jnp.zeros((n, e, capacity), jnp.float32)
    combine = jnp.zeros((n, e, capacity), jnp.float32)
    for slot in range(k):
        oh = _one_hot(gidx[:, slot], e)           # [N, E]
        pos = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]
        keep = (pos < capacity).astype(jnp.float32) * oh
        counts = counts + keep.sum(axis=0)
        pos_kept = (pos * keep).sum(-1).astype(jnp.int32)  # [N]
        slot_disp = keep[:, :, None] * _one_hot(pos_kept, capacity)[:, None]
        dispatch = dispatch + slot_disp
        combine = combine + gval[:, slot, None, None] * slot_disp

    # switch-style load balancing on the top-1 assignment
    importance = gates.mean(axis=0)               # [E]
    load = _one_hot(gidx[:, 0], e).mean(axis=0)   # [E]
    aux = e * jnp.sum(importance * load)
    return dispatch, combine, aux


class MoEMLP(Layer):
    """Expert-parallel feed-forward mixture — drop-in for a dense FFN.

    Expert weights are stacked ``[E, ...]``; ``shard(mesh, ep_axis)`` pins
    ``Shard(0)`` so each ep rank owns ``E/ep`` experts (the reference's
    per-rank expert placement, ``moe_layer.py`` MoELayer). After forward,
    ``self.aux_loss`` holds the load-balance loss of the last call."""

    def __init__(self, hidden_size, intermediate_size, num_experts,
                 top_k=2, capacity_factor=1.25, mesh=None, ep_axis="ep",
                 weight_attr=None):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.gate = Linear(hidden_size, num_experts, bias_attr=False,
                           weight_attr=weight_attr)
        e, h, i = num_experts, hidden_size, intermediate_size
        from ....nn import initializer as I
        init = (weight_attr if weight_attr is not None
                else I.Normal(std=0.02))

        def mk(shape):
            return Parameter(init(shape, jnp.float32), trainable=True)

        self.w1 = mk((e, h, i))
        self.b1 = Parameter(jnp.zeros((e, i), jnp.float32), trainable=True)
        self.w2 = mk((e, i, h))
        self.b2 = Parameter(jnp.zeros((e, h), jnp.float32), trainable=True)
        self.aux_loss = None
        if mesh is not None:
            self.shard(mesh, ep_axis)

    def shard(self, mesh, ep_axis="ep"):
        from ....distributed.auto_parallel.api import (Replicate, Shard,
                                                       shard_parameter)
        dim = mesh.dim_names.index(ep_axis)
        pl = [Replicate()] * mesh.ndim
        pl[dim] = Shard(0)
        for p in (self.w1, self.b1, self.w2, self.b2):
            shard_parameter(p, mesh, pl)
        return self

    def forward(self, x):
        e, k, cf = self.num_experts, self.top_k, self.capacity_factor
        shape = tuple(x.shape)
        n_tokens = int(shape[0] if len(shape) == 2
                       else math.prod(shape[:-1]))
        capacity = max(int(math.ceil(k * n_tokens / e * cf)), 1)

        def impl(xv, wg, w1, b1, w2, b2):
            flat = xv.reshape(n_tokens, xv.shape[-1])
            logits = flat @ wg
            gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            dispatch, combine, aux = moe_dispatch_combine(gates, k, capacity)
            # [N,E,C] x [N,H] -> [E,C,H]: the all-to-all point (XLA emits
            # it when flat is dp-sharded and w1 is ep-sharded)
            expert_in = jnp.einsum("nec,nh->ech", dispatch,
                                   flat.astype(jnp.float32))
            hdn = jax.nn.gelu(
                jnp.einsum("ech,ehi->eci", expert_in, w1) + b1[:, None])
            y = jnp.einsum("eci,eih->ech", hdn, w2) + b2[:, None]
            out = jnp.einsum("nec,ech->nh", combine, y)
            return out.astype(xv.dtype).reshape(shape), aux

        out, aux = apply("moe_mlp", impl, x, self.gate.weight, self.w1,
                         self.b1, self.w2, self.b2)
        self.aux_loss = aux
        return out


class MoELayer(Layer):
    """Reference ``MoELayer`` parity surface: wraps a gate spec + expert
    shape into the einsum-dispatch ``MoEMLP``. ``gate`` may be "switch"
    (top-1) or "gshard" (top-2), matching the reference gate classes."""

    def __init__(self, d_model, d_hidden, num_experts, gate="gshard",
                 capacity_factor=1.25, mesh=None, ep_axis="ep",
                 recompute_interval=0, **kwargs):
        super().__init__()
        if isinstance(gate, str):
            if gate not in ("switch", "gshard", "naive"):
                raise ValueError(f"unknown gate {gate!r}")
            top_k = 1 if gate == "switch" else 2
        else:
            top_k = int(getattr(gate, "top_k", 2))
        self.moe = MoEMLP(d_model, d_hidden, num_experts, top_k=top_k,
                          capacity_factor=capacity_factor, mesh=mesh,
                          ep_axis=ep_axis)

    @property
    def aux_loss(self):
        return self.moe.aux_loss

    def forward(self, x):
        return self.moe(x)


# ---------------------------------------------------------------------------
# Dropless sparse block: one chip's share of an expert-parallel layer
# ---------------------------------------------------------------------------
_CARRY = 1 << 30    # the tally's low word holds less than this
# sorted slot rows a chunk holds: gathered, multiplied, weighted and
# handed back together, or skipped together (v5e, PR 30: PERF.md)
_SLOTS_AT_A_TIME = 8192
_CALLS_KEPT = 1024  # calls whose own tallies a block keeps, a row each
# layer -> the per-call buffer of the block last built under that name:
# the buffer, not the block, for the gauges' reason
_calls_of = {}


def _chunks(slots, at_a_time):
    """The chunks ``slots`` sorted slots are cut into: the fewest of at
    most ``at_a_time`` (None: ``_SLOTS_AT_A_TIME``) that divide them."""
    chunks = -(-slots // (at_a_time or _SLOTS_AT_A_TIME))
    while slots % chunks:
        chunks += 1
    return chunks


def _chunk(c, slots, order, weight, routed):
    """Chunk ``c`` of the ``slots``-long cuts of the sorted slots: its
    tokens, its combine weights and which of its rows hold a slot
    routed here (all of them, except in the last chunk that runs)."""
    lo = c * slots
    slot = jax.lax.dynamic_slice(order, (lo,), (slots,))
    return (slot // weight.shape[-1], jnp.take(weight.reshape(-1), slot),
            lo + jnp.arange(slots) < routed)


def _each_live_chunk(live, run, init):
    """``run(c, carry)`` for every chunk ``c`` that ``live`` [chunks]
    marks, in order; the others are skipped."""
    def body(c, carry):
        return jax.lax.cond(live[c], lambda v: run(c, v), lambda v: v, carry)

    return jax.lax.fori_loop(0, live.shape[0], body, init)


def takes_grouped_kernel(slots, held, hidden, width):
    """Whether a chunk of ``slots`` rows through ``held`` experts
    ``hidden`` -> ``width`` -> ``hidden`` makes its products by the
    kernels of ``ops/pallas/grouped_matmul.py``: in a ``to_static``
    program captured on a TPU, at extents the kernels tile (multiples
    of 128).  Any other backend, any other extent and per-op dispatch
    (a program's eager first call, where every call of a kernel is
    traced and lowered anew: PERF.md section 6, PR 45) keep
    ``jax.lax.ragged_dot``."""
    from ....ops.pallas import grouped_matmul
    return (jax.default_backend() == "tpu" and _scope.current() is not None
            and grouped_matmul.takes(slots, held, hidden, width)
            and grouped_matmul.takes(slots, held, width, hidden))


def _expert_rows(rows, w, w1, w3, w2, groups, mine, kernel):
    """A chunk's token rows through their experts, group by group, each
    scaled by its slot's combine weight: float32 [slots, H].

    ``kernel`` True: the three products and, in the backward, their six
    gradients are ``ops/pallas/grouped_matmul.py``'s (``grouped_dot``),
    which reads no row past the last group and WRITES ZEROS there, in
    the result and in the rows' gradient, and adds nothing of them to
    the weights': no select is needed, and ``mine`` is not read.  False:
    ``jax.lax.ragged_dot``, whose kernel on the chip leaves such rows
    unwritten, and what is left there is not a number to be multiplied,
    even by zero: the selects take it out of the result, of the rows'
    gradient and of the weights' before anything is scaled."""
    if kernel:
        from ....ops.pallas import grouped_matmul
        # one pair of step plans for the chunk's nine products
        with _scope.phase("expert_mlp"):
            dot = functools.partial(
                grouped_matmul.grouped_dot,
                plans=grouped_matmul.make_plans(groups, rows.shape[0]))
    else:
        dot = jax.lax.ragged_dot
        with _scope.phase("dispatch"):
            rows = jnp.where(mine[:, None], rows, 0)
    with _scope.phase("expert_mlp"):
        a = dot(rows, w1, groups)
        b = dot(rows, w3, groups)
        y = dot(jax.nn.silu(a) * b, w2, groups)
    with _scope.phase("combine"):
        if not kernel:
            y = jnp.where(mine[:, None], y, 0)
        return w[:, None] * y.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10,))
def _routed_rows(flat, weight, w1, w3, w2, order, inverse, sizes, routed,
                 live, kernel):
    """float32 [N, H]: per token, the sum over its slots routed here of
    weight x experts(row).  ``order`` sorts the N * k slots by held
    expert (absent experts last) and ``inverse`` is its inverse;
    ``sizes`` [chunks, held] cuts the groups at chunk edges; the first
    ``routed`` sorted slots are routed here and ``live`` [chunks] says
    which chunks hold any of them; ``kernel`` is ``_expert_rows``'
    choice of products.  The row work is done chunk by
    chunk under ``live``: a chunk past the routed prefix is skipped,
    forward and backward, so the gathers, products, selects and sums
    follow the routed count and not the dropless worst case N * k.

    A chunk's weighted rows are scatter-added to their tokens in one
    float32 accumulator, and the gradient gathers the same rows back;
    the chunk's products are recomputed in its own backward, so its
    temporaries are never held for two chunks at once."""
    return _routed_rows_fwd(flat, weight, w1, w3, w2, order, inverse, sizes,
                            routed, live, kernel)[0]


def _routed_rows_fwd(flat, weight, w1, w3, w2, order, inverse, sizes, routed,
                     live, kernel):
    slots = order.shape[0] // sizes.shape[0]

    def run(c, out):
        with _scope.phase("dispatch"):
            at, w, mine = _chunk(c, slots, order, weight, routed)
            rows = jnp.take(flat, at, axis=0)
        y = _expert_rows(rows, w, w1, w3, w2, sizes[c], mine, kernel)
        with _scope.phase("combine"):
            return out.at[at].add(y, mode="promise_in_bounds")

    out = _each_live_chunk(live, run, jnp.zeros(flat.shape, jnp.float32))
    return out, (flat, weight, w1, w3, w2, order, inverse, sizes, routed,
                 live)


def _routed_rows_bwd(kernel, res, g):
    flat, weight, w1, w3, w2, order, inverse, sizes, routed, live = res
    slots = order.shape[0] // sizes.shape[0]

    def run(c, grads):
        d_flat, d_ws, d1, d3, d2 = grads
        with _scope.phase("dispatch"):
            at, w, mine = _chunk(c, slots, order, weight, routed)
            rows = jnp.take(flat, at, axis=0)
        with _scope.phase("combine"):
            g_rows = jnp.take(g, at, axis=0)
        _, back = jax.vjp(
            lambda *a: _expert_rows(*a, sizes[c], mine, kernel),
            rows, w, w1, w3, w2)
        d_rows, d_w, e1, e3, e2 = back(g_rows)
        with _scope.phase("dispatch"):
            d_flat = d_flat.at[at].add(d_rows.astype(jnp.float32),
                                       mode="promise_in_bounds")
        with _scope.phase("combine"):
            d_ws = d_ws.at[c].set(d_w)
        with _scope.phase("expert_mlp"):
            return d_flat, d_ws, d1 + e1, d3 + e3, d2 + e2

    d_flat, d_ws, d1, d3, d2 = _each_live_chunk(
        live, run,
        (jnp.zeros(flat.shape, jnp.float32),
         jnp.zeros((sizes.shape[0], slots), weight.dtype),
         jnp.zeros_like(w1), jnp.zeros_like(w3), jnp.zeros_like(w2)))
    # a slot's weight gradient back at its own place: ``inverse`` makes
    # the transpose of the permutation a gather too
    with _scope.phase("combine"):
        d_weight = jnp.take(d_ws.reshape(-1), inverse).reshape(weight.shape)
    return (d_flat.astype(flat.dtype), d_weight, d1, d3, d2,
            None, None, None, None, None)


_routed_rows.defvjp(_routed_rows_fwd, _routed_rows_bwd)


def sparse_moe(x, gate, w1, w3, w2, *, bias, top_k, expert_offset,
               scaling=1.0, norm_eps=1e-6, scoring="sigmoid",
               train_router=True, slots_at_a_time=None,
               grouped_kernel=False):
    """Values in, ``(out, tally, chunks)`` out; the math of
    ``SparseMoEBlock``.

    ``x`` [..., H]; ``gate`` [H, E] over all E experts; ``w1``/``w3``
    [held, H, I] and ``w2`` [held, I, H] are the experts
    ``expert_offset .. expert_offset + held`` ; ``bias`` [E] float32;
    ``norm_eps`` is added to the selected scores' sum before they are
    divided by it (the caller's family's: 1e-6 ``lfm2_moe``, 1e-20
    ``deepseek_v3``, 0 ``mellum``); ``scoring`` is the caller's family's
    too: ``"sigmoid"`` of each logit, or ``"softmax"`` over all E.
    ``train_router`` False makes the combine weights constants of the
    backward, so that neither ``gate`` nor ``x`` takes a gradient
    through them (``SparseMoEBlock`` says when); ``slots_at_a_time``
    is the most sorted slots a chunk holds (None: ``_SLOTS_AT_A_TIME``).
    ``grouped_kernel`` True makes a chunk's products by the Pallas
    kernels of ``ops/pallas/grouped_matmul.py`` and not by
    ``jax.lax.ragged_dot`` (``SparseMoEBlock`` says when).  ``tally``
    is int32 [held + 1]: the slots routed to each held expert
    and, last, the slots the router filled (N * top_k).  ``chunks`` is
    int32 [2]: the chunks of sorted slots whose rows were worked on, and
    the chunks there were."""
    held, k = w1.shape[0], top_k
    flat = x.reshape(-1, x.shape[-1])
    n = flat.shape[0]
    with _scope.phase("router"):
        logits = jnp.dot(flat, gate, preferred_element_type=jnp.float32)
        scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        # the bias decides WHICH experts, never how much of each
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weight = picked / (picked.sum(-1, keepdims=True) + norm_eps) \
            * scaling
        if not train_router:
            weight = jax.lax.stop_gradient(weight)
    with _scope.phase("dispatch"):
        local = chosen - expert_offset                        # [N, k]
        here = (local >= 0) & (local < held)
        # slots of absent experts sort last, behind every group
        key = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(key)
        inverse = jnp.argsort(order)
        counts = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        routed = counts.sum()
        # all N * k sorted slots would be worked on only if every token
        # chose all its experts here: the chunks, and the conditions
        # the row work runs under
        chunks = _chunks(n * k, slots_at_a_time)
        slots = n * k // chunks
        lo = (jnp.arange(chunks) * slots)[:, None]
        live = lo[:, 0] < routed
        ends = jnp.cumsum(counts)
        sizes = jnp.clip(ends[None], lo, lo + slots) \
            - jnp.clip((ends - counts)[None], lo, lo + slots)
    out = _routed_rows(flat, weight, w1, w3, w2, order, inverse, sizes,
                       routed, live, bool(grouped_kernel))
    tally = jnp.concatenate([counts, jnp.full((1,), n * k, jnp.int32)])
    ran = jnp.stack([live.sum(dtype=jnp.int32), jnp.int32(chunks)])
    return out.astype(x.dtype).reshape(x.shape), tally, ran


def _tally(routed):
    hi, lo = np.asarray(jax.device_get(routed._read()), np.int64)
    return [int(v) for v in hi * _CARRY + lo]


def _share(tally):
    *here, filled = tally
    return sum(here) / filled if filled else 0.0


def _run_share(chunks):
    ran, there = (int(v) for v in jax.device_get(chunks._read()))
    return ran / there if there else 0.0


def routed_by_call():
    """{layer: {call number, from 1: that call's tally}} for the last
    ``_CALLS_KEPT`` calls each sparse block has counted (one host read
    a layer).  The gauges say what was routed since construction; this
    says when, so that a reader can take the calls of the stretch it
    timed: a router that trains moves its load."""
    out = {}
    for layer, kept in _calls_of.items():
        rows = np.asarray(jax.device_get(kept._read()), np.int64)
        out[layer] = {int(row[-1]): [int(v) for v in row[:-1]]
                      for row in rows if row[-1]}
    return out


class SparseMoEBlock(Layer):
    """Dropless top-k block that is told which experts it holds.

    The router scores ALL ``num_experts`` in float32 (``scoring``:
    ``"sigmoid"`` of each logit, the default, or ``"softmax"`` over them
    all); selection
    adds ``expert_bias`` (a float32 buffer no gradient reaches: the
    trainer's balancing rule owns it), the combine weights are the
    selected scores normalised to sum to one (their sum plus
    ``norm_eps``), times ``routed_scaling_factor``.  The block holds
    the SwiGLU experts
    ``expert_offset .. expert_offset + experts_held`` stacked
    ``[held, ...]`` and returns THEIR part of the layer's result: the
    slots routed here are gathered in expert order and multiplied group
    by group, whatever the imbalance; there is no capacity and no
    dropped token.  The grouped products are the Pallas kernels of
    ``ops/pallas/grouped_matmul.py`` in a ``to_static`` program
    captured on a TPU (tiles that divide the widths they are handed;
    zeros written past the last group) and ``jax.lax.ragged_dot`` on
    any other backend, at an extent that is no multiple of 128 and in
    per-op dispatch (``takes_grouped_kernel``); the gauge
    ``moe.grouped_kernel{layer}`` reads 1 where the program captured
    last took the kernels and 0 where it kept ``ragged_dot``.  What the
    absent experts would add is left out: under expert parallelism the
    shares are summed across chips, and on one chip the block runs
    without that exchange.

    Two arguments are for a block that runs as such a lone share.
    ``train_router`` False: the combine weights are constants of the
    backward.  A share knows ``dL/dw_e`` of the experts it holds and
    reads the absent ones' as zero, so the router's gradient it can
    form is not its part of the deployment's but one that moves the
    routed share itself (PERF.md section 6, PR 42: 6 x in 130 steps, or
    to nothing); the deployment's balancing rule, which a share has
    not, is what holds it.  ``slots_at_a_time``: a chunk's rows are
    worked on or skipped together, so a chunk must not end AT the load
    the share expects (an even spread's ``N * top_k * held /
    num_experts``), or one slot more doubles the row work.

    ``tally()`` is what was routed here since construction: the compiled
    step adds ``held + 1`` integers to a small buffer, and the
    ``moe.tokens_per_expert{layer,expert}`` /
    ``moe.routed_here_share{layer}`` gauges of the ``observability``
    registry read it only when a snapshot is taken.  ``routed_by_call()``
    is the same by call: the step also writes the call's own tally into
    one row of a ring.  ``moe.slot_rows_run_share{layer}`` is how much
    of the dropless worst case's row work was done: the chunks of sorted
    slots that ran over the chunks there were, since construction."""

    def __init__(self, hidden_size, intermediate_size, num_experts, top_k,
                 expert_offset=0, experts_held=None,
                 routed_scaling_factor=1.0, expert_bias=None,
                 weight_attr=None, down_attr=None, name=None,
                 norm_eps=1e-6, scoring="sigmoid", train_router=True,
                 slots_at_a_time=None):
        super().__init__()
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring {scoring!r}: 'sigmoid' or 'softmax'")
        self.scoring, self.train_router = scoring, bool(train_router)
        self.slots_at_a_time = slots_at_a_time
        held = num_experts - expert_offset if experts_held is None \
            else experts_held
        if not (0 <= expert_offset and 0 < held
                and expert_offset + held <= num_experts):
            raise ValueError(
                f"experts {expert_offset}..{expert_offset + held} are not "
                f"among the router's {num_experts}")
        if not 0 < top_k <= num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.expert_offset, self.experts_held = expert_offset, held
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_eps = float(norm_eps)
        self.gate = Linear(hidden_size, num_experts, bias_attr=False,
                           weight_attr=weight_attr)
        h, i = hidden_size, intermediate_size
        self.w1 = self.create_parameter([held, h, i], attr=weight_attr)
        self.w3 = self.create_parameter([held, h, i], attr=weight_attr)
        self.w2 = self.create_parameter([held, i, h],
                                        attr=down_attr or weight_attr)
        bias = np.zeros(num_experts, np.float32) if expert_bias is None \
            else np.asarray(expert_bias, np.float32)
        if bias.shape != (num_experts,):
            raise ValueError(f"expert_bias {bias.shape} for a router over "
                             f"{num_experts}")
        # a constant of the configuration while this block trains, so
        # not a leaf of the checkpoint; float32 under AMP O2 because
        # ``decorate`` casts parameters only
        self.register_buffer("expert_bias", Tensor(jnp.asarray(bias)),
                             persistable=False)
        # [2, held + 1]: high and low words (``_CARRY`` each) of the
        # slots per held expert and of the slots the router filled
        self.register_buffer(
            "routed", Tensor(jnp.zeros((2, held + 1), jnp.int32)),
            persistable=False)
        # a ring of the last calls' own tallies, each with its number
        # (from 1; 0 marks a row not written yet)
        self.register_buffer(
            "calls", Tensor(jnp.zeros((_CALLS_KEPT, held + 2), jnp.int32)),
            persistable=False)
        # the chunks of sorted slots that ran, and the chunks there were
        self.register_buffer("chunks", Tensor(jnp.zeros((2,), jnp.int32)),
                             persistable=False)
        # [1]: whether the program captured last took the kernels
        self._took_kernel = [0]
        layer = name or self._full_name
        _calls_of[layer] = self.calls
        self._register_gauges(layer)

    def _register_gauges(self, layer):
        from ....observability import metrics
        # the gauges hold the tally's 72-byte buffer, not the block: the
        # registry outlives the block and must not keep its weights
        # alive, and a snapshot taken after the model is gone still
        # reads what was routed
        reg, routed, chunks = metrics.registry(), self.routed, self.chunks
        took = self._took_kernel
        for e in range(self.experts_held):
            reg.gauge(
                "moe.tokens_per_expert",
                "token slots routed to an expert held here",
                labels={"layer": layer, "expert": self.expert_offset + e}
            ).set_function(lambda e=e: _tally(routed)[e])
        reg.gauge(
            "moe.routed_here_share",
            "share of the router's slots that went to experts held here",
            labels={"layer": layer}
        ).set_function(lambda: _share(_tally(routed)))
        reg.gauge(
            "moe.slot_rows_run_share",
            "share of the sorted slot rows whose chunk was worked on",
            labels={"layer": layer}
        ).set_function(lambda: _run_share(chunks))
        reg.gauge(
            "moe.grouped_kernel",
            "1 where the program captured last made the grouped products "
            "by the Pallas kernels, 0 where by ragged_dot",
            labels={"layer": layer}
        ).set_function(lambda: took[0])

    def tally(self):
        """Python ints [held + 1]: slots per held expert, then the
        slots the router filled, since construction (one host read)."""
        return _tally(self.routed)

    def routed_here_share(self):
        return _share(self.tally())

    def count(self, tally, chunks):
        """Add one call's ``tally`` and ``chunks`` (the second and third
        result of ``forward``) to the buffers.  Apart from ``forward``
        because a write made inside a ``recompute`` region does not
        leave it: a block that is recomputed returns them and counts
        them outside."""
        with no_grad():
            self.chunks._write(self.chunks._read() + chunks._read())
            tally = tally._read()
            hi, lo = self.routed._read()
            lo = lo + tally
            carry = lo // _CARRY
            self.routed._write(jnp.stack([hi + carry, lo - carry * _CARRY]))
            kept = self.calls._read()
            n = kept[:, -1].max()
            row = jnp.concatenate([tally, (n + 1)[None]])
            self.calls._write(jax.lax.dynamic_update_slice(
                kept, row[None], (n % kept.shape[0], jnp.zeros_like(n))))

    def forward(self, x):
        """(this chip's part of the layer's result, the call's tally,
        the chunks it ran and had)."""
        slots = math.prod(x.shape[:-1]) * self.top_k
        kernel = takes_grouped_kernel(
            slots // _chunks(slots, self.slots_at_a_time), *self.w1.shape)
        if _scope.current() is not None:
            self._took_kernel[0] = int(kernel)
        return apply(
            "sparse_moe",
            functools.partial(sparse_moe, top_k=self.top_k,
                              expert_offset=self.expert_offset,
                              scaling=self.routed_scaling_factor,
                              norm_eps=self.norm_eps,
                              scoring=self.scoring,
                              train_router=self.train_router,
                              slots_at_a_time=self.slots_at_a_time,
                              grouped_kernel=kernel),
            x, self.gate.weight, self.w1, self.w3, self.w2,
            bias=self.expert_bias)
