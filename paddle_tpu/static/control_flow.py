"""Control-flow ops: ``cond`` / ``while_loop`` / ``switch_case`` / ``case``.

Capability analog of the reference's control-flow layer
(``python/paddle/static/nn/control_flow.py:1444`` cond, ``:687`` while_loop,
``:1065`` switch_case, ``:942`` case), TPU-native in mechanism: instead of
ConditionalBlock/While ops inside a ProgramDesc, these lower onto
``lax.cond`` / ``lax.while_loop`` / ``lax.switch`` so a jit-captured train
step keeps data-dependent branching *inside* the single compiled XLA
program — the gap that previously forced a permanent eager fallback.

Semantics by execution mode (mirrors the reference's dygraph/static split):

- **Eager (dygraph)**: the predicate is concrete; exactly one branch runs,
  with full per-op autograd. Identical to the reference's dygraph behavior.
- **Under jit capture** (``paddle.jit.to_static`` discovery or replay): a
  real ``lax.cond``/``switch``/``while`` is emitted through the op funnel.
  Both/all branches are traced (the reference's static mode builds both
  blocks too); closed-over tensors (weights etc.) are discovered by a probe
  pass and hoisted into explicit operands so capture registers them as
  program inputs and gradients flow through ``jax.vjp`` of the whole op.

XLA constraints (documented divergences from the PIR executor):

- Branches must return the same structure with matching shapes/dtypes
  (static-shape compilation; the reference's runtime branch selection can
  tolerate shape mismatch, XLA cannot).
- Branch bodies must be functional under capture: in-place writes to
  tensors that exist outside the branch raise (a traced branch cannot
  mutate framework state; the same code still works eagerly). This includes
  the global RNG — use dropout outside branches or pass explicit seeds.
- ``while_loop`` under capture compiles to ``lax.while_loop`` when no
  operand needs gradients. When gradients ARE required (XLA has no
  reverse-mode while) it lowers to a **bounded ``lax.scan`` with
  early-exit masking**: the scan runs ``max_trip_count`` iterations
  (default from ``FLAGS_while_grad_max_trip_count``), each step applies
  the body only while the predicate still held (``jnp.where`` select on
  every carry leaf), so the loop stays inside the compiled program and
  differentiates through the selected iterations — the capability analog
  of the reference's differentiable While op
  (``python/paddle/static/nn/control_flow.py:687``). A loop still live
  at the bound warns at runtime (``jax.debug.callback``) and returns the
  truncated carry.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core import state
from ..core import tensor as tensor_mod
from ..core.dispatch import apply, unwrap
from ..core.tensor import Tensor

__all__ = ["cond", "while_loop", "switch_case", "case"]


# --------------------------------------------------------------------------
# tracker shims
# --------------------------------------------------------------------------

class _BranchTracker:
    """Tracker installed while a branch body runs under capture.

    - substitutes hoisted operand values (``subs``: id(Tensor) -> value),
    - tracks branch-local tensors so their in-place writes stay local,
    - records ordered reads of outer tensors when probing,
    - forbids mutation of outer state (not representable in lax.cond).
    """

    def __init__(self, base, subs, record=False):
        self.base = base
        self.subs = subs
        self.record = record
        self.reads: list[Tensor] = []       # ordered, unique (probe mode)
        self._read_ids: set[int] = set()
        self.local: set[int] = set()
        self.local_env: dict[int, Any] = {}

    def on_create(self, t):
        self.local.add(id(t))
        if self.base is not None:
            self.base.on_create(t)

    def substituted(self, t):
        """A fused-optimizer member view reads through this tracker
        when it holds a value for it (optimizer/flat.py)."""
        return id(t) in self.subs or id(t) in self.local_env

    def on_read(self, t):
        tid = id(t)
        if tid in self.subs:
            return self.subs[tid]
        if tid in self.local_env:
            return self.local_env[tid]
        if tid in self.local:
            return t._data
        if self.record and tid not in self._read_ids:
            self._read_ids.add(tid)
            self.reads.append(t)
        if self.base is not None:
            return self.base.on_read(t)
        return t._data

    def on_write(self, t, val):
        tid = id(t)
        if tid in self.local or tid in self.subs:
            self.local_env[tid] = val
            return
        raise RuntimeError(
            "control flow: in-place write to a tensor defined outside the "
            "branch/body is not supported under jit capture (a traced "
            "lax.cond/while branch cannot mutate framework state); return "
            "the new value from the branch instead")

    def on_grad_write(self, t):
        raise RuntimeError(
            "control flow: .backward() inside a branch/body is not "
            "supported; call it on the result of cond/while_loop")

    def add_host_sync(self, fn):
        if self.base is not None:
            self.base.add_host_sync(fn)


def _run_branch(fn: Callable, subs, record=False):
    """Run ``fn()`` under a _BranchTracker with grad recording off (the
    outer op's jax.vjp owns differentiation) and flatten the result *inside*
    the tracker context (branch-local in-place writes live in the tracker's
    local_env, not in Tensor._data). Returns (leaves, tree, tracker)."""
    tr = _BranchTracker(tensor_mod._tracker, subs, record=record)
    old = tensor_mod.set_tracker(tr)
    prev = state.set_grad_enabled(False)
    try:
        out = fn()
        leaves, tree = _flatten_out(out)
    finally:
        state.set_grad_enabled(prev)
        tensor_mod.set_tracker(old)
    return leaves, tree, tr


def _hoist(fns):
    """Probe every branch once, collecting the ordered union of
    outer-tensor reads (weights and other closures) to hoist as explicit
    operands. Returns (trees, reads, leaves-per-fn)."""
    reads: list[Tensor] = []
    read_ids: set[int] = set()
    trees = []
    leaves_all = []
    for fn in fns:
        leaves, tree, tr = _run_branch(fn, {}, record=True)
        trees.append(tree)
        leaves_all.append(leaves)
        for t in tr.reads:
            if id(t) not in read_ids:
                read_ids.add(id(t))
                reads.append(t)
    return trees, reads, leaves_all


# --------------------------------------------------------------------------
# undefined-slot unification (dy2static support)
#
# dy2static's escape elimination (early return / break / continue -> flag
# form) can leave a state slot holding the UNDEF sentinel on one branch
# while the other branch binds it to a tensor (the reference fills such
# slots with RETURN_NO_VALUE / UndefinedVar dummies,
# ``python/paddle/jit/dy2static/return_transformer.py``). When the caller
# passes ``_undef_fill``, slots that are UNDEF on one side and a tensor on
# the other are filled with typed zeros — semantically dead values, guarded
# by the flag that accompanies them.
# --------------------------------------------------------------------------

def _tree_has(tree, sentinel):
    kind = tree[0]
    if kind == "c":
        return tree[1] is sentinel
    if kind in ("list", "tuple"):
        return any(_tree_has(t, sentinel) for t in tree[1])
    if kind == "dict":
        return any(_tree_has(t, sentinel) for t in tree[1].values())
    return False


def _needs_unify(a, b, sentinel):
    """True when the trees disagree at a position the fill can repair:
    sentinel-vs-anything or plain-scalar-constant-vs-tensor."""
    ka, kb = a[0], b[0]
    if ka == "c" and (a[1] is sentinel
                      or (kb == "T" and isinstance(a[1],
                                                   (bool, int, float)))):
        return True
    if kb == "c" and (b[1] is sentinel
                      or (ka == "T" and isinstance(b[1],
                                                   (bool, int, float)))):
        return True
    if ka == kb == "c" and isinstance(a[1], (bool, int, float)) \
            and isinstance(b[1], (bool, int, float)) and a[1] != b[1]:
        return True
    if ka == kb and ka in ("list", "tuple") and len(a[1]) == len(b[1]):
        return any(_needs_unify(x, y, sentinel)
                   for x, y in zip(a[1], b[1]))
    if ka == kb == "dict":
        return any(_needs_unify(a[1][k], b[1][k], sentinel)
                   for k in a[1] if k in b[1])
    return False


def _sub_fill(obj, other_tree, other_leaves, sentinel):
    """Replace ``sentinel`` leaves of ``obj`` with typed zeros (or the
    matching constant) taken from the corresponding position of the
    other branch's probe; promote plain scalar constants paired with a
    tensor on the other side (a converted flag set like ``brk = True``
    is a python constant in one branch and a carried tensor in the
    other)."""
    if obj is sentinel:
        if other_tree[0] == "T":
            ref = other_leaves[other_tree[1]]
            return Tensor(jnp.zeros(jnp.shape(ref),
                                    getattr(ref, "dtype", None)
                                    or jnp.result_type(ref)))
        if other_tree[0] == "c" and isinstance(other_tree[1],
                                               (bool, int, float)):
            return other_tree[1]
        return obj
    if isinstance(obj, (bool, int, float)) and other_tree[0] == "T":
        ref = other_leaves[other_tree[1]]
        return Tensor(jnp.asarray(obj, getattr(ref, "dtype", None)
                                  or jnp.result_type(ref)))
    if isinstance(obj, (bool, int, float)) and other_tree[0] == "c" \
            and isinstance(other_tree[1], (bool, int, float)) \
            and obj != other_tree[1]:
        # branches bind the SAME name to DIFFERENT constants (cont=True
        # in one arm, the False reset in the other): only a traced
        # select can represent the merge
        return Tensor(jnp.asarray(obj, jnp.result_type(obj,
                                                       other_tree[1])))
    if isinstance(obj, (list, tuple)) and other_tree[0] in ("list", "tuple") \
            and len(other_tree[1]) == len(obj):
        return type(obj)(_sub_fill(o, t, other_leaves, sentinel)
                         for o, t in zip(obj, other_tree[1]))
    if isinstance(obj, dict) and other_tree[0] == "dict":
        return {k: (_sub_fill(v, other_tree[1][k], other_leaves, sentinel)
                    if k in other_tree[1] else v)
                for k, v in obj.items()}
    return obj


def _filled_fn(fn, other_tree, other_leaves, sentinel):
    def wrapped():
        return _sub_fill(fn(), other_tree, other_leaves, sentinel)
    return wrapped


# --------------------------------------------------------------------------
# output-structure handling
# --------------------------------------------------------------------------

def _flatten_out(out):
    """nest of Tensors/values -> (flat jax values, treedef with holes).

    Must run while the tracker that produced ``out`` is active: values are
    taken through ``_read`` so substitutions and branch-local writes
    resolve."""
    leaves = []

    def go(o):
        if isinstance(o, Tensor):
            leaves.append(o._read())
            return ("T", len(leaves) - 1)
        if isinstance(o, (list, tuple)):
            return (type(o).__name__, [go(x) for x in o])
        if isinstance(o, dict):
            return ("dict", {k: go(o[k]) for k in sorted(o)})
        return ("c", o)

    tree = go(out)
    return leaves, tree


def _rebuild_out(tree, tensors):
    kind = tree[0]
    if kind == "T":
        return tensors[tree[1]]
    if kind == "list":
        return [_rebuild_out(t, tensors) for t in tree[1]]
    if kind == "tuple":
        return tuple(_rebuild_out(t, tensors) for t in tree[1])
    if kind == "dict":
        return {k: _rebuild_out(v, tensors) for k, v in tree[1].items()}
    return tree[1]


def _struct_sig(tree):
    kind = tree[0]
    if kind == "T":
        return "T"
    if kind in ("list", "tuple"):
        return (kind, tuple(_struct_sig(t) for t in tree[1]))
    if kind == "dict":
        return ("dict", tuple((k, _struct_sig(v))
                              for k, v in sorted(tree[1].items())))
    v = tree[1]
    if isinstance(v, (np.ndarray, jax.Array)):  # value-compare raw arrays
        a = np.asarray(v)
        return ("arr", a.shape, str(a.dtype), a.tobytes())
    try:
        hash(v)
        return ("c", v)
    except TypeError:
        return ("c", type(v).__name__, repr(v)[:200])


def _check_same_structure(trees, what):
    sigs = [_struct_sig(t) for t in trees]
    if any(s != sigs[0] for s in sigs[1:]):
        raise ValueError(
            f"{what}: branches must return the same structure of tensors "
            f"(got {sigs})")


def _as_bool_scalar(v):
    return jnp.reshape(jnp.asarray(v), ()).astype(bool)


def _needs_grad(tensors):
    return state.is_grad_enabled() and any(
        isinstance(t, Tensor) and not t.stop_gradient for t in tensors)


# --------------------------------------------------------------------------
# cond
# --------------------------------------------------------------------------

def cond(pred, true_fn=None, false_fn=None, name=None, return_names=None,
         _undef_fill=None):
    """``true_fn()`` if ``pred`` else ``false_fn()`` (reference
    ``static/nn/control_flow.py:1444``). Works eagerly (runs one branch)
    and under jit capture (emits ``lax.cond``)."""
    true_fn = true_fn if true_fn is not None else (lambda: None)
    false_fn = false_fn if false_fn is not None else (lambda: None)
    if not callable(true_fn) or not callable(false_fn):
        raise TypeError("cond: true_fn and false_fn must be callable")

    if tensor_mod._tracker is None:
        return true_fn() if bool(unwrap(pred)) else false_fn()

    trees, reads, leaves = _hoist([true_fn, false_fn])
    tree_t, tree_f = trees
    if _undef_fill is not None and _needs_unify(tree_t, tree_f,
                                                _undef_fill):
        true_fn = _filled_fn(true_fn, tree_f, leaves[1], _undef_fill)
        false_fn = _filled_fn(false_fn, tree_t, leaves[0], _undef_fill)
        trees, reads, leaves = _hoist([true_fn, false_fn])
        tree_t, tree_f = trees
    _check_same_structure([tree_t, tree_f], "cond")

    pred_t = pred if isinstance(pred, Tensor) else Tensor(jnp.asarray(pred))
    read_ids = [id(t) for t in reads]

    def _cond_impl(pred_v, *op_vals):
        def mk(fn):
            def branch(vals):
                leaves, _, _ = _run_branch(fn, dict(zip(read_ids, vals)))
                return tuple(leaves)
            return branch
        return jax.lax.cond(_as_bool_scalar(pred_v), mk(true_fn),
                            mk(false_fn), tuple(op_vals))

    flat = apply("cond", _cond_impl, pred_t, *reads)
    return _rebuild_out(tree_t, list(flat))


# --------------------------------------------------------------------------
# switch_case / case
# --------------------------------------------------------------------------

def _normalize_branch_fns(branch_fns, default):
    if isinstance(branch_fns, dict):
        pairs = sorted(branch_fns.items())
    elif isinstance(branch_fns, (list, tuple)):
        if branch_fns and not isinstance(branch_fns[0], (list, tuple)):
            pairs = list(enumerate(branch_fns))
        else:
            pairs = sorted((int(k), fn) for k, fn in branch_fns)
    else:
        raise TypeError("switch_case: branch_fns must be dict|list|tuple")
    keys = [k for k, _ in pairs]
    if len(set(keys)) != len(keys):
        raise ValueError(f"switch_case: duplicate branch keys {keys}")
    for _, fn in pairs:
        if not callable(fn):
            raise TypeError("switch_case: branch fns must be callable")
    if default is None:
        default = pairs[-1][1]  # reference: max index wins when no match
    elif not callable(default):
        raise TypeError("switch_case: default must be callable")
    return pairs, default


def switch_case(branch_index, branch_fns, default=None, name=None):
    """C-style switch (reference ``static/nn/control_flow.py:1065``):
    run ``branch_fns[branch_index]``, else ``default``."""
    pairs, default = _normalize_branch_fns(branch_fns, default)

    if tensor_mod._tracker is None:
        idx = int(unwrap(branch_index))
        for k, fn in pairs:
            if k == idx:
                return fn()
        return default()

    fns = [fn for _, fn in pairs] + [default]
    keys = [k for k, _ in pairs]
    trees, reads, _ = _hoist(fns)
    _check_same_structure(trees, "switch_case")

    idx_t = (branch_index if isinstance(branch_index, Tensor)
             else Tensor(jnp.asarray(branch_index)))
    read_ids = [id(t) for t in reads]

    def _switch_impl(idx_v, *op_vals):
        iv = jnp.reshape(jnp.asarray(idx_v), ()).astype(jnp.int32)
        sel = jnp.full((), len(keys), jnp.int32)  # default slot
        for i, k in enumerate(keys):
            sel = jnp.where(iv == k, jnp.int32(i), sel)

        def mk(fn):
            def branch(vals):
                leaves, _, _ = _run_branch(fn, dict(zip(read_ids, vals)))
                return tuple(leaves)
            return branch

        return jax.lax.switch(sel, [mk(f) for f in fns], tuple(op_vals))

    flat = apply("switch_case", _switch_impl, idx_t, *reads)
    return _rebuild_out(trees[0], list(flat))


def case(pred_fn_pairs, default=None, name=None):
    """if/elif/else chain (reference ``static/nn/control_flow.py:942``):
    first true pred wins; ``default`` (or the last fn) when none is."""
    if not isinstance(pred_fn_pairs, (list, tuple)) or not pred_fn_pairs:
        raise TypeError("case: pred_fn_pairs must be a non-empty list|tuple")
    for p in pred_fn_pairs:
        if not (isinstance(p, (list, tuple)) and len(p) == 2
                and callable(p[1])):
            raise TypeError("case: elements must be (pred, callable) pairs")
    preds = [p for p, _ in pred_fn_pairs]
    fns = [fn for _, fn in pred_fn_pairs]
    if default is None:
        default = fns[-1]

    if tensor_mod._tracker is None:
        for p, fn in zip(preds, fns):
            if bool(unwrap(p)):
                return fn()
        return default()

    all_fns = list(fns) + [default]
    trees, reads, _ = _hoist(all_fns)
    _check_same_structure(trees, "case")

    pred_ts = [p if isinstance(p, Tensor) else Tensor(jnp.asarray(p))
               for p in preds]
    read_ids = [id(t) for t in reads]
    n = len(fns)

    def _case_impl(*vals):
        pred_vs, op_vals = vals[:n], vals[n:]
        stacked = jnp.stack([_as_bool_scalar(p) for p in pred_vs]
                            + [jnp.asarray(True)])
        sel = jnp.argmax(stacked).astype(jnp.int32)  # first True wins

        def mk(fn):
            def branch(ops):
                leaves, _, _ = _run_branch(fn, dict(zip(read_ids, ops)))
                return tuple(leaves)
            return branch

        return jax.lax.switch(sel, [mk(f) for f in all_fns], tuple(op_vals))

    flat = apply("case", _case_impl, *pred_ts, *reads)
    return _rebuild_out(trees[0], list(flat))


# --------------------------------------------------------------------------
# while_loop
# --------------------------------------------------------------------------

def while_loop(cond, body, loop_vars, is_test=False, name=None,
               max_trip_count=None, _undef_fill=None):
    """Repeat ``body`` while ``cond`` holds (reference
    ``static/nn/control_flow.py:687``).

    ``max_trip_count`` (extension): trip bound used only for the
    differentiable lowering under jit capture; defaults to
    ``FLAGS_while_grad_max_trip_count``."""
    if not callable(cond) or not callable(body):
        raise TypeError("while_loop: cond and body must be callable")
    if not isinstance(loop_vars, (list, tuple)) or not loop_vars:
        raise TypeError("while_loop: loop_vars must be a non-empty "
                        "list|tuple")
    # Python-scalar loop vars become Tensors so the carry stays a traced
    # leaf (a plain `0` counter would otherwise be a changing constant and
    # trip the structure check under capture).
    loop_vars = type(loop_vars)(_tensorize(v) for v in loop_vars)

    def run_python_loop():
        vars_ = tuple(loop_vars)
        while bool(unwrap(cond(*vars_))):
            out = body(*vars_)
            if not isinstance(out, (list, tuple)):
                out = (out,)
            if len(out) != len(vars_):
                raise ValueError(
                    "while_loop: body must return as many values as "
                    f"loop_vars (got {len(out)}, want {len(vars_)})")
            vars_ = tuple(out)
        return list(vars_) if isinstance(loop_vars, list) else vars_

    if tensor_mod._tracker is None:
        return run_python_loop()

    # ---- capture: probe for closed-over invariants and the carry tree
    carry_leaves, carry_tree = _flatten_out(tuple(loop_vars))
    carry_ts = list(_iter_tensors(loop_vars))
    carry_ids = [id(t) for t in carry_ts]

    def probe_body():
        out = body(*loop_vars)
        return tuple(out) if isinstance(out, (list, tuple)) else (out,)

    (_, body_tree), reads, bleaves = _hoist([lambda: cond(*loop_vars),
                                             probe_body])
    if _undef_fill is not None and body_tree[0] in ("tuple", "list") \
            and len(body_tree[1]) == len(loop_vars) \
            and _needs_unify(carry_tree, body_tree, _undef_fill):
        # two repairable disagreements between carry and body:
        # - a slot UNDEF at entry that becomes a tensor inside the body
        #   (__pt_retv before the first early return): seed the carry
        #   with typed zeros from the body probe;
        # - a slot that is a tensor in the carry but a python constant
        #   in the body output (a flag reset like ``cont = False``):
        #   promote the body's constant to the carry's tensor type.
        loop_vars = type(loop_vars)(
            _sub_fill(v, t, bleaves[1], _undef_fill)
            for v, t in zip(loop_vars, body_tree[1]))
        carry_leaves, carry_tree = _flatten_out(tuple(loop_vars))
        carry_ts = list(_iter_tensors(loop_vars))
        carry_ids = [id(t) for t in carry_ts]
        orig_body, final_tree, final_leaves = body, carry_tree, carry_leaves

        def body(*vs):
            out = orig_body(*vs)
            out = tuple(out) if isinstance(out, (list, tuple)) else (out,)
            return tuple(_sub_fill(o, t, final_leaves, _undef_fill)
                         for o, t in zip(out, final_tree[1]))

        (_, body_tree), reads, bleaves = _hoist([lambda: cond(*loop_vars),
                                                 probe_body])
    _check_same_structure([carry_tree, body_tree], "while_loop")
    reads = [t for t in reads if id(t) not in set(carry_ids)]
    n_carry = len(carry_leaves)

    needs_grad = _needs_grad(carry_ts + reads)
    if needs_grad:
        bound = int(max_trip_count
                    if max_trip_count is not None
                    else state.get_flag("while_grad_max_trip_count"))
        if bound <= 0:
            # explicit opt-out of the scan lowering: Python unroll during
            # discovery -> to_static eager fallback on replay, where the
            # loop differentiates through the tape
            return run_python_loop()

    def _make_cond_body(vals):
        # keyed from the tensors themselves, which this closure so keeps
        # alive: eagerly the op is linearised at BACKWARD time
        # (dispatch.apply), after ``loop_vars`` died, and the id of a freed
        # loop var is then taken by a tensor the body makes (``i + 1``'s
        # constant read as ``i``: every trip up to the bound runs)
        inv = dict(zip(map(id, reads), vals[n_carry:]))

        def wrap_vars(carry):
            ts = [Tensor(v) for v in carry]
            return _rebuild_out(carry_tree, ts)

        def subs_for(carry):
            # closures over the ORIGINAL loop-var objects see the current
            # carry (the static-mode semantics: the var IS the loop slot)
            s = dict(inv)
            s.update(zip(map(id, carry_ts), carry))
            return s

        def cond_w(carry):
            leaves, _, _ = _run_branch(
                lambda: cond(*_as_tuple(wrap_vars(carry))),
                subs_for(carry))
            return _as_bool_scalar(leaves[0])

        def body_w(carry):
            def run():
                out = body(*_as_tuple(wrap_vars(carry)))
                return tuple(out) if isinstance(out, (list, tuple)) \
                    else (out,)
            leaves, _, _ = _run_branch(run, subs_for(carry))
            return tuple(leaves)

        return cond_w, body_w

    def _while_impl(*vals):
        cond_w, body_w = _make_cond_body(vals)
        return jax.lax.while_loop(cond_w, body_w, tuple(vals[:n_carry]))

    def _while_scan_impl(*vals):
        # differentiable lowering: bounded scan, body masked off once the
        # predicate first fails (reverse-mode flows through the selected
        # iterations only; jnp.where's vjp routes zero cotangent to the
        # unselected branch)
        cond_w, body_w = _make_cond_body(vals)
        init = tuple(vals[:n_carry])

        def step(carry, _):
            done, vars_ = carry
            live = jnp.logical_and(jnp.logical_not(done), cond_w(vars_))
            new_vars = body_w(vars_)
            sel = tuple(jnp.where(live, n, o)
                        for n, o in zip(new_vars, vars_))
            return (jnp.logical_or(done, jnp.logical_not(live)), sel), None

        (done, final), _ = jax.lax.scan(
            step, (jnp.zeros((), bool), init), None, length=bound)
        still_live = jnp.logical_and(jnp.logical_not(done), cond_w(final))

        def _warn(live):
            if bool(live):
                # PDT206 through the graph-lint registry: honors the
                # PDTPU_ANALYSIS mode flag and analysis.suppress()
                from ..analysis import report_runtime
                report_runtime(
                    "PDT206",
                    "while_loop: differentiable scan lowering hit its "
                    f"trip bound ({bound}) with the predicate still "
                    "true; result is truncated. Raise max_trip_count or "
                    "FLAGS_while_grad_max_trip_count.",
                    file="<while_loop>")
        jax.debug.callback(_warn, still_live)
        return final

    flat = apply("while_loop",
                 _while_scan_impl if needs_grad else _while_impl,
                 *carry_ts, *reads)
    res = _rebuild_out(carry_tree, list(flat))
    return list(res) if isinstance(loop_vars, list) else res


def _as_tuple(x):
    return x if isinstance(x, tuple) else tuple(x)


def _tensorize(v):
    """Promote scalar/array loop vars to Tensors; leave nests to the user
    (the reference requires loop_vars to be Variables too)."""
    if isinstance(v, Tensor) or isinstance(v, (list, tuple, dict)):
        return v
    if isinstance(v, (bool, int, float, np.ndarray, np.generic, jax.Array)):
        return Tensor(jnp.asarray(v))
    return v


def _iter_tensors(obj):
    """Tensor leaves in _flatten_out's traversal order (same walk as
    jit._flatten_tensors; kept in lock-step with _flatten_out because
    carry ids are zipped positionally against carry leaves)."""
    from ..jit import _flatten_tensors
    return iter(_flatten_tensors(obj, []))
