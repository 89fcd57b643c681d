"""Grouped matrix products: rows sorted by group, one weight a group.

``grouped_dot(rows [M, K], w [G, K, N], group_sizes [G]) -> [M, N]`` is
``jax.lax.ragged_dot``: the first ``group_sizes[0]`` rows times ``w[0]``,
the next ``group_sizes[1]`` times ``w[1]``, and so on; what a dropless
mixture of experts runs over the slots routed to the experts a chip holds
(``incubate/distributed/models/moe.py`` ``_expert_rows``).  Three Pallas
kernels in the design of megablox (``jax.experimental.pallas.ops.tpu.
megablox``) and of the kernel the TPU's compiler itself makes of
``ragged_dot``: a plan of grid steps computed from ``group_sizes`` in the
program and scalar-prefetched, each step one (row tile, group) pair, so
that the row work follows ``sum(group_sizes)``:

* ``grouped_matmul_fwd``  ``rows x w[g]``            -> [M, N]
* ``grouped_matmul_dx``   ``dy x w[g]^T``, from ``w`` AS IT IS STORED
  (a product contracted over both operands' last dimension; no [G, N, K]
  copy)                                              -> [M, K]
* ``grouped_matmul_dw``   ``rows^T x dy`` a group    -> [G, K, N], one
  float32 accumulator per (group, K tile, N tile) over the group's row
  tiles, rounded once at the store; zeros for a group with no rows.

Operands in their own type (bfloat16 under AMP O2), float32 accumulation,
results in the operands' type.  **Rows past the last group are written as
zeros** by the first two and read by none of the three: a NaN there, in
``rows`` or ``dy``, reaches no result (``ragged_dot`` on the chip leaves
such rows unwritten).

What differs from both models is the tile rule (``tiles``).  The
compiler's kernel takes each of (rows, K, N) as the largest of {512, 256,
128} that divides it, so an expert width of 896 = 7 x 128 or 1408 = 11 x
128 runs in 128-wide tiles: the rows are read from HBM again for each of
7 or 11 column tiles and a grid step moves more bytes than it multiplies
(PERF.md section 6, PR 46).  Here a width is tiled by ANY multiple of 128
that divides it, whole where the VMEM budget allows: with N and K whole a
call reads rows, weights and result once, and a group's weight stays in
VMEM from one row tile to the next.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# What a grid step may hold in VMEM, double-buffered operands and result
# plus the float32 accumulator (``vmem_bytes``); the v5e has 128 MiB and
# Mosaic gives a kernel 16 MiB of it unless told, so every call states
# its need plus ``_HEADROOM`` for the body's own temporaries (an operand
# turned for a product contracted over rows, the masked operands).
_BUDGET = 40 << 20
_HEADROOM = 24 << 20


# ------------------------------------------------------------ the tile rule
def _divisors(n):
    """The tiles a width of ``n`` may take, largest first: the multiples
    of 128 that divide it, or ``n`` whole where it is no such multiple
    (a block equal to the array is always a legal one)."""
    if n % _LANES:
        return [n]
    return [d for d in range(n, 0, -_LANES) if n % d == 0]


def _row_tile(m):
    """256 rows (PERF.md section 6, PR 46: the sweep).  A group's weight
    stays in VMEM from one row tile to the next, so a smaller tile costs
    no refetch, and a tile cut by a group's edge is multiplied whole
    once for each group in it: 256 reads 4-12% under 512 at 8 groups and
    within 3% of 128 at 32, and 1024 reads 25-40% over."""
    if m % _LANES:
        return m
    return 256 if m % 256 == 0 else _LANES


def vmem_bytes(kind, tm, tk, tn, itemsize=2):
    """Bytes a grid step of kernel ``kind`` holds: both operands' tiles
    and the result's twice (Pallas double-buffers each) and the float32
    accumulator (where K is whole the row kernels have none: the
    float32 product before it is rounded, then)."""
    if kind == "dw":        # rows [tm, tk], dy [tm, tn] -> [tk, tn]
        return 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
    # fwd: rows [tm, tk] x w [tk, tn]; dx: dy [tm, tk] x w^T [tn, tk]
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def _traffic(kind, m, g, k, n, tm, tk, tn):
    """Elements a call moves to and from HBM at these tiles when every
    row is routed: what the rule minimises."""
    nk, nn = k // tk, n // tn
    if kind == "dw":        # rows once an N tile, dy once a K tile
        return m * k * nn + m * n * nk + g * k * n
    # a group's weight stays while K is whole; cut, every row tile
    # fetches it again
    weights = g if nk == 1 else m // tm + g - 1
    return m * k * nn + weights * k * n + m * n


def tiles(kind, m, g, k, n, itemsize=2):
    """``(tm, tk, tn)`` for kernel ``kind`` ("fwd", "dx" or "dw") at
    ``rows [m, k]``, ``g`` groups, ``w [g, k, n]``: for "dx" ``tk`` cuts
    the contracted width ``n`` and ``tn`` the result's ``k``.  ``tk`` and
    ``tn`` divide their widths and are multiples of 128 (a width that is
    no multiple is taken whole); of the pairs whose step fits ``_BUDGET``
    the one that moves the fewest bytes, then the one with the fewest
    steps.  Static: every argument is a shape."""
    tm = _row_tile(m)
    if kind == "dx":
        k, n = n, k
    fits = [(tk, tn) for tk in _divisors(k) for tn in _divisors(n)
            if vmem_bytes(kind, tm, tk, tn, itemsize) <= _BUDGET]
    if not fits:
        fits = [(_divisors(k)[-1], _divisors(n)[-1])]
    tk, tn = min(fits, key=lambda t: (_traffic(kind, m, g, k, n, tm, *t),
                                      -t[0] * t[1]))
    return tm, tk, tn


def takes(m, g, k, n):
    """Whether the kernels take ``rows [m, k] x w [g, k, n]`` on the
    chip: every extent a multiple of 128 (Mosaic's tiling)."""
    return not (m % _LANES or k % _LANES or n % _LANES)


# ------------------------------------------------------------ the step plan
def _running(x):
    """``cumsum`` of a short int32 vector as one masked sum."""
    at = jnp.arange(x.shape[0])
    return jnp.sum(jnp.where(at[None, :] <= at[:, None], x[None, :], 0),
                   axis=1)


@functools.partial(jax.jit, static_argnames=("m", "tm", "tail"))
def _plan(group_sizes, *, m, tm, tail):
    """The grid's row steps, int32, to be scalar-prefetched: ``lo``,
    ``hi`` [G, and one more with ``tail``], the rows ``lo[g] <= row <
    hi[g]`` of group ``g``; ``group``, ``tile`` [m / tm + G - 1], the
    group and the row tile of each step; ``total`` [1], the steps that
    are real.  A group visits every row tile that holds a row of it, so
    a tile cut by group edges is visited once for each group in it; at
    most G - 1 visits are such second ones, which is the bound.  A step
    past ``total`` repeats the last real one, so that no block index
    moves and no DMA is started.

    ``tail`` True (the row kernels): a group with no rows visits
    nothing, and group number G stands for the rows past the last
    group, which owns the whole tiles past them and no row (``lo`` =
    ``hi``): its steps store zeros.  False (the weights' kernel): a
    group with no rows visits one tile, of which it owns no row, so
    that its zeros are stored; the rows past the last group are visited
    by nobody.

    Sums over small masks, no ``cumsum`` and no gather: a handful of
    small fusions a plan."""
    g, tiles_m = group_sizes.shape[0], m // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = _running(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles_m - 1)
    visits = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm,
                       0 if tail else 1)
    lo, hi = starts, ends
    if tail:
        past = jnp.minimum((ends[-1:] + tm - 1) // tm, tiles_m)
        first = jnp.concatenate([first, past])
        visits = jnp.concatenate([visits, tiles_m - past])
        edge = jnp.full((1,), m, jnp.int32)
        lo, hi = jnp.concatenate([lo, edge]), jnp.concatenate([hi, edge])
    done = _running(visits)
    total = done[-1:]
    step = jnp.minimum(jnp.arange(tiles_m + g - 1, dtype=jnp.int32),
                       total - 1)
    group = jnp.sum(step[:, None] >= done[None, :], axis=1,
                    dtype=jnp.int32)
    # a group's first tile less the steps before the group's own
    shift = first - (done - visits)
    tile = step + jnp.sum(
        jnp.where(group[:, None] == jnp.arange(shift.shape[0])[None, :],
                  shift[None, :], 0), axis=1)
    return lo, hi, group, tile, total


def make_plans(group_sizes, m):
    """The two step plans of ``m`` rows in these groups: the row
    kernels' and the weights' kernel's.  Every product of one set of
    rows shares them (a chunk's three products, their recompute and
    their six gradients: ``moe._expert_rows``)."""
    tm = _row_tile(m)
    return (_plan(group_sizes, m=m, tm=tm, tail=True),
            _plan(group_sizes, m=m, tm=tm, tail=False))


def _owned(lo_ref, hi_ref, tile_ref, s, g, tm):
    """[tm, 1] bool: the rows of step ``s``'s tile that group ``g``
    owns."""
    row = tile_ref[s] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= lo_ref[g]) & (row < hi_ref[g])


# ------------------------------------------------------- rows x w, dy x w^T
def _rows_kernel(lo_ref, hi_ref, group_ref, tile_ref, total_ref,
                 x_ref, w_ref, o_ref, *acc, tm, held, k_steps, turned):
    """One (row tile, group) step of ``x [tm, tk] x w[g] [tk, tn]``, or
    with ``turned`` of ``x [tm, tk] x w[g] [tn, tk]^T``, summed over the
    innermost grid dimension's K tiles in ``acc`` (absent where K is
    whole)."""
    s, ki = pl.program_id(1), pl.program_id(2)
    g = group_ref[s]
    live = s < total_ref[0]
    dims = (((1,), (1,)), ((), ())) if turned else (((1,), (0,)), ((), ()))

    def product():
        return jax.lax.dot_general(x_ref[...], w_ref[...], dims,
                                   preferred_element_type=jnp.float32)

    def store(y):
        # the first visit of a row tile leaves zeros in the rows the
        # group does not own; a later one keeps what is there
        fresh = (s == 0) | (tile_ref[s] != tile_ref[jnp.maximum(s - 1, 0)])
        kept = jnp.where(fresh, 0.0, o_ref[...].astype(jnp.float32))
        mine = _owned(lo_ref, hi_ref, tile_ref, s, g, tm)
        o_ref[...] = jnp.where(mine, y, kept).astype(o_ref.dtype)

    real = live & (g < held)
    if k_steps == 1:
        @pl.when(real)
        def _():
            store(product())
    else:
        acc_ref, = acc

        @pl.when(real & (ki == 0))
        def _():
            acc_ref[...] = product()

        @pl.when(real & (ki > 0))
        def _():
            acc_ref[...] += product()

        @pl.when(real & (ki == k_steps - 1))
        def _():
            store(acc_ref[...])

    @pl.when(live & (g == held) & (ki == k_steps - 1))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit,
                   static_argnames=("turned", "interpret", "tiling"))
def _rows_call(x, w, plan, *, turned, interpret, tiling=None):
    """``x [M, K] x w [G, K, N]``, or with ``turned`` ``x [M, N] x w [G,
    K, N]^T`` -> [M, K], by the row kernels' ``plan``.  A ``jax.jit`` of
    its own: a program's many calls at one shape are traced and lowered
    once (96 calls of six shapes in Mellum2's step: un-jitted they put
    +15 to +21% on a warm ``setup_s``, PERF.md section 6, PR 46)."""
    from . import out_struct
    m, kc = x.shape
    held = w.shape[0]
    n = w.shape[1] if turned else w.shape[2]
    kind = "dx" if turned else "fwd"
    tm, tk, tn = tiling or tiles(kind, m, held, *w.shape[1:],
                                 x.dtype.itemsize)
    k_steps, n_steps, row_steps = kc // tk, n // tn, m // tm + held - 1

    # a step that makes no product (one past the real ones, or one that
    # stores the zeros of the rows past the last group) holds the
    # operands' blocks where the last product left them: no DMA
    def at_k(s, ki, group, total):
        return jnp.where((s < total[0]) & (group[s] < held), ki,
                         k_steps - 1)

    def x_at(ni, s, ki, lo, hi, group, tile, total):
        last = jnp.maximum((hi[held - 1] + tm - 1) // tm - 1, 0)
        return (jnp.where(group[s] < held, tile[s], last),
                at_k(s, ki, group, total))

    def w_at(ni, s, ki, lo, hi, group, tile, total):
        gi, at = jnp.minimum(group[s], held - 1), at_k(s, ki, group, total)
        return (gi, ni, at) if turned else (gi, at, ni)

    def o_at(ni, s, ki, lo, hi, group, tile, total):
        return tile[s], ni

    need = vmem_bytes(kind, tm, tk, tn, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, held=held, k_steps=k_steps,
                          turned=turned),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_steps, row_steps, k_steps),
            in_specs=[pl.BlockSpec((tm, tk), x_at),
                      pl.BlockSpec((None, tn, tk) if turned
                                   else (None, tk, tn), w_at)],
            out_specs=pl.BlockSpec((tm, tn), o_at),
            scratch_shapes=[] if k_steps == 1
            else [pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=out_struct((m, n), x.dtype, x, w),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=need + _HEADROOM),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * kc * n, transcendentals=0,
            bytes_accessed=x.dtype.itemsize * _traffic(
                kind, m, held, kc, n, tm, tk, tn)),
        interpret=interpret,
        name="grouped_matmul_dx" if turned else "grouped_matmul_fwd",
    )(*plan, x, w)


# ------------------------------------------------------------ rows^T x dy
def _weights_kernel(lo_ref, hi_ref, group_ref, tile_ref, total_ref,
                    x_ref, dy_ref, o_ref, acc_ref, *, tm):
    """One (group, row tile) step of ``x [tm, tk]^T x dy [tm, tn]``,
    summed over the group's steps, which are consecutive on the
    innermost grid dimension."""
    s = pl.program_id(2)
    g, total = group_ref[s], total_ref[0]
    live = s < total

    @pl.when(live & ((s == 0) | (group_ref[jnp.maximum(s - 1, 0)] != g)))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live & (hi_ref[g] > lo_ref[g]))
    def _():
        # both operands: a zero against a NaN is a NaN
        mine = _owned(lo_ref, hi_ref, tile_ref, s, g, tm)
        x = jnp.where(mine, x_ref[...], 0).astype(x_ref.dtype)
        dy = jnp.where(mine, dy_ref[...], 0).astype(dy_ref.dtype)
        acc_ref[...] += jax.lax.dot_general(
            x, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    last = pl.num_programs(2) - 1
    @pl.when(live & ((s == total - 1)
                     | (group_ref[jnp.minimum(s + 1, last)] != g)))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "tiling"))
def _weights_call(x, dy, plan, *, interpret, tiling=None):
    """``x [M, K]^T x dy [M, N]`` group by group -> [G, K, N], by the
    weights' kernel's ``plan``; a ``jax.jit`` of its own as
    ``_rows_call`` is."""
    from . import out_struct
    (m, k), n, held = x.shape, dy.shape[1], plan[0].shape[0]
    tm, tk, tn = tiling or tiles("dw", m, held, k, n, x.dtype.itemsize)
    need = vmem_bytes("dw", tm, tk, tn, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_weights_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, k // tk, m // tm + held - 1),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, ki, s, lo, hi, group,
                             tile, total: (tile[s], ki)),
                pl.BlockSpec((tm, tn), lambda ni, ki, s, lo, hi, group,
                             tile, total: (tile[s], ni))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda ni, ki, s, lo, hi, group, tile,
                total: (group[s], ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=out_struct((held, k, n), x.dtype, x, dy),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=need + _HEADROOM),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=x.dtype.itemsize * _traffic(
                "dw", m, held, k, n, tm, tk, tn)),
        interpret=interpret,
        name="grouped_matmul_dw",
    )(*plan, x, dy)


# --------------------------------------------------------------- the entry
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_dot(rows, w, both, interpret):
    return _rows_call(rows, w, both[0], turned=False, interpret=interpret)


def _grouped_dot_fwd(rows, w, both, interpret):
    return _grouped_dot(rows, w, both, interpret), (rows, w, both)


def _grouped_dot_bwd(interpret, kept, dy):
    rows, w, both = kept
    return (_rows_call(dy, w, both[0], turned=True, interpret=interpret),
            _weights_call(rows, dy, both[1], interpret=interpret), None)


_grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


def grouped_dot(rows, w, group_sizes, plans=None, interpret=None):
    """``rows [M, K]`` times ``w [G, K, N]`` group by group ->
    ``[M, N]`` in ``rows``' type: ``jax.lax.ragged_dot``, but for the
    rows past ``sum(group_sizes)``, which are exact zeros in the result
    and in the rows' gradient and add nothing to the weights', whatever
    ``rows`` and the result's cotangent hold there.  Differentiable in
    ``rows`` and ``w``.  ``plans``: ``make_plans(group_sizes, M)``, for
    a caller that multiplies one set of rows more than once; made here
    where it is None."""
    if (rows.ndim != 2 or w.ndim != 3 or rows.shape[1] != w.shape[1]
            or group_sizes.shape != w.shape[:1] or rows.dtype != w.dtype):
        raise ValueError(f"grouped_dot: rows {rows.shape} {rows.dtype}, "
                         f"w {w.shape} {w.dtype}, "
                         f"group_sizes {group_sizes.shape}")
    if interpret is None:
        from . import use_interpret
        interpret = use_interpret()
    if plans is None:
        plans = make_plans(group_sizes, rows.shape[0])
    return _grouped_dot(rows, w, plans, bool(interpret))
