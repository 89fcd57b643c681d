"""AST front-end: tracer-safety checks (``PDT1xx``).

These run over a function's source *before* ``jit.to_static`` conversion
and flag the patterns the dy2static rewriter either silently falls back
on (graph breaks) or that trace to something the author did not mean
(host syncs baked into the compiled program, trace-time-only side
effects, host randomness captured as a constant).

A check is a generator ``check(fndef, ctx) -> (node, message)`` where
``fndef`` is the (possibly nested) ``ast.FunctionDef`` being linted in a
jit context and ``ctx`` carries filename/source. Severity and code come
from the registry entry.
"""
from __future__ import annotations

import ast
import copy

from ..core.state import PREFIX_CACHE_OFF_SPELLINGS
from .registry import Severity, decorator_name, register

_HOST_SYNC_METHODS = {"numpy", "item", "tolist"}
_MUTATORS = {"append", "extend", "insert", "remove", "clear", "update",
             "add", "setdefault"}
_HOST_ENTROPY_ROOTS = {"random", "time"}


def _walk_fn(fndef):
    """Walk the function's own scope only — nested defs are NOT
    descended into: the engine lints every nested function as its own
    jit scope, so a nested def's suppression (decorator tag, def-line
    pragma) governs its own findings."""
    stack = [fndef]
    while stack:
        node = stack.pop()
        if node is not fndef and isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _dotted(node) -> str | None:
    """``a.b.c`` attribute chain -> ``"a.b.c"`` (None if not a chain)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@register(
    "PDT101", "host-sync-in-jit", Severity.WARN, "ast",
    example="""
import paddle_tpu as paddle

@paddle.jit.to_static
def step(x):
    y = x * 2
    return y.numpy()
""",
    near_miss="""
def step(x):
    y = x * 2
    return y.numpy()
""")
def check_host_sync(fndef, ctx):
    """``.numpy()``/``.item()``/``.tolist()`` or ``float()``/``int()``/
    ``bool()`` on a traced value inside a jit function blocks on a
    device->host transfer and graph-breaks the capture — the single
    costliest silent hazard on a network-attached TPU."""
    for node in _walk_fn(fndef):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _HOST_SYNC_METHODS \
                and not node.args and not node.keywords:
            yield node, (f".{f.attr}() inside a jit function forces a "
                         f"device->host sync (graph break); keep the "
                         f"value on device or move the call outside "
                         f"to_static")
        elif isinstance(f, ast.Name) and f.id in ("float", "int", "bool") \
                and len(node.args) == 1 and not node.keywords \
                and isinstance(node.args[0], ast.Call) \
                and isinstance(node.args[0].func, ast.Attribute):
            # only the tensor-shaped pattern float(x.sum()): a bare
            # float(name) is usually a plain Python scalar conversion
            yield node, (f"{f.id}() on a tensor expression forces a "
                         f"device->host sync inside a jit function; use "
                         f"tensor ops (astype/comparison) instead")


@register(
    "PDT102", "print-in-traced-code", Severity.NOTE, "ast",
    example="""
from paddle_tpu.jit import to_static

@to_static
def step(x):
    print(x)
    return x * 2
""",
    near_miss="""
from paddle_tpu.jit import to_static

@to_static
def step(x):
    log(x)
    return x * 2
""")
def check_print(fndef, ctx):
    """``print`` inside traced code runs at trace time only: it fires
    once per compile, not once per step, and printing a tensor shows a
    tracer, not values. Use a host callback or move it out of jit."""
    for node in _walk_fn(fndef):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "print":
            yield node, ("print() in traced code runs once per compile, "
                         "not per step; it will show tracers, not values")


@register(
    "PDT103", "global-write-in-jit", Severity.WARN, "ast",
    example="""
import paddle_tpu as paddle

@paddle.jit.to_static
def step(x):
    global counter
    counter = counter + 1
    return x * 2
""",
    near_miss="""
import paddle_tpu as paddle

@paddle.jit.to_static
def step(x):
    counter = 1
    return x * counter
""")
def check_global_write(fndef, ctx):
    """Writing a ``global`` from a jit function is a trace-time side
    effect: the write happens once per compile, and replaying the cached
    program never updates it again."""
    for node in _walk_fn(fndef):
        if isinstance(node, ast.Global):
            yield node, (f"global write ({', '.join(node.names)}) in a "
                         f"jit function happens at trace time only; the "
                         f"cached program will not repeat it")


@register(
    "PDT104", "mutation-in-converted-branch", Severity.NOTE, "ast",
    example="""
import paddle_tpu as paddle

@paddle.jit.to_static
def step(x, acc):
    if x.mean() > 0:
        acc.append(x)
    return x * 2
""",
    near_miss="""
import paddle_tpu as paddle

@paddle.jit.to_static
def step(x, acc):
    acc.append(x)
    if x.mean() > 0:
        x = x + 1
    return x * 2
""")
def check_branch_mutation(fndef, ctx):
    """Container mutation (``.append``/``.update``/...) inside an
    ``if``/``while`` body: if the predicate is a tensor, dy2static
    traces BOTH branches, so the mutation runs even when its branch is
    not taken — and runs once per trace, not per step."""

    compound = (ast.If, ast.While, ast.For, ast.With, ast.Try,
                ast.AsyncFor, ast.AsyncWith)

    def scan(stmts, in_branch):
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            if in_branch and not isinstance(s, compound):
                for node in ast.walk(s):
                    if isinstance(node, ast.Call) \
                            and isinstance(node.func, ast.Attribute) \
                            and node.func.attr in _MUTATORS:
                        yield (node,
                               f".{node.func.attr}() inside a converted "
                               f"branch replays at trace time for both "
                               f"sides of the predicate")
            branch_here = in_branch or isinstance(s, (ast.If, ast.While))
            for blk in _stmt_blocks(s):
                yield from scan(blk, branch_here)

    yield from scan(fndef.body, False)


def _stmt_blocks(s):
    for attr in ("body", "orelse", "finalbody"):
        blk = getattr(s, attr, None)
        if isinstance(blk, list) and blk and isinstance(blk[0], ast.stmt):
            yield blk
    for h in getattr(s, "handlers", []) or []:
        yield h.body


@register(
    "PDT105", "graph-break-escape", Severity.WARN, "ast",
    example="""
import paddle_tpu as paddle

@paddle.jit.to_static
def step(x):
    if x.mean() > 0:
        with open("/tmp/f") as f:
            return x * 2
    return x
""",
    near_miss="""
import paddle_tpu as paddle

@paddle.jit.to_static
def step(x):
    if x.mean() > 0:
        return x * 2
    return x
""")
def check_graph_break_escape(fndef, ctx):
    """A control-flow site dy2static cannot convert (``return``/``break``
    beyond what the escape-elimination passes handle, ``del``, ``yield``,
    loop ``else``) is silently left as plain Python: a tensor predicate
    there graph-breaks the whole capture. This check replays the real
    dy2static transformer pipeline and flags the sites that survive it
    unconverted."""
    from ..jit.dy2static import (_BreakContinueEliminator, _ForEachDesugar,
                                 _eliminate_returns, _has_escape,
                                 _is_range_for, _visit_body,
                                 _walk_in_scope)
    fd = copy.deepcopy(fndef)
    try:
        _visit_body(_ForEachDesugar(), fd)
        _eliminate_returns(fd)
        _visit_body(_BreakContinueEliminator(), fd)
        ast.fix_missing_locations(fd)
    except Exception:
        return  # conversion machinery declined outright; PDT107 covers it
    seen = set()
    for s in fd.body:
        for node in _walk_in_scope(s):
            broke = False
            if isinstance(node, ast.If):
                broke = _has_escape(node.body) or _has_escape(node.orelse)
            elif isinstance(node, ast.While):
                broke = bool(node.orelse) or _has_escape(node.body,
                                                         loop_ctx=True)
            elif isinstance(node, ast.For):
                broke = _is_range_for(node) and _has_escape(node.body,
                                                            loop_ctx=True)
            if broke and (node.lineno, node.col_offset) not in seen:
                seen.add((node.lineno, node.col_offset))
                kind = type(node).__name__.lower()
                yield node, (f"`{kind}` block contains an escape "
                             f"(return/break/del/yield past what escape "
                             f"elimination handles): dy2static leaves it "
                             f"as plain Python — a tensor predicate here "
                             f"graph-breaks the capture")


@register(
    "PDT106", "host-entropy-in-jit", Severity.WARN, "ast",
    example="""
import random
import paddle_tpu as paddle

@paddle.jit.to_static
def step(x):
    return x * random.random()
""",
    near_miss="""
import random
import paddle_tpu as paddle

def make_noise():
    return random.random()

@paddle.jit.to_static
def step(x):
    return x * 2.0
""")
def check_host_entropy(fndef, ctx):
    """``random.*`` / ``time.*`` / ``np.random.*`` in traced code is
    evaluated once at trace time and baked into the compiled program as
    a constant — every subsequent step reuses the same 'random' value.
    Use ``paddle.seed`` + framework random ops instead."""
    for node in _walk_fn(fndef):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if not dotted:
            continue
        parts = dotted.split(".")
        hostile = (parts[0] in _HOST_ENTROPY_ROOTS and len(parts) > 1) or \
            (parts[0] in ("np", "numpy") and len(parts) > 2
             and parts[1] == "random")
        if hostile:
            yield node, (f"{dotted}() runs at trace time: the value is "
                         f"baked into the compiled program as a constant "
                         f"(same 'random' number every step)")


@register(
    "PDT107", "unconvertible-function", Severity.WARN, "ast",
    example="""
import paddle_tpu as paddle

def outer():
    k = 0

    @paddle.jit.to_static
    def step(x):
        nonlocal k
        k += 1
        return x * 2
    return step
""",
    near_miss="""
import paddle_tpu as paddle

def outer():
    k = 2

    @paddle.jit.to_static
    def step(x):
        return x * k
    return step
""")
def check_unconvertible(fndef, ctx):
    """Function-level features that make dy2static decline the WHOLE
    function (``nonlocal`` writes, ``__name``-mangled attributes,
    decorators it cannot strip): tensor control flow inside then always
    falls back to eager with no conversion at all."""
    from ..jit.dy2static import _has_mangled_names
    for node in _walk_fn(fndef):
        if isinstance(node, ast.Nonlocal):
            yield node, (f"nonlocal ({', '.join(node.names)}) makes "
                         f"dy2static decline the whole function (re-exec "
                         f"cannot share closure cells for writes)")
    if _has_mangled_names(fndef):
        yield fndef, ("__name-mangled attribute access does not survive "
                      "dy2static's re-exec; the function is left "
                      "unconverted")
    if ctx.decorated:
        for dec in fndef.decorator_list:
            name = decorator_name(dec)
            if name not in ("to_static", "suppress"):
                yield dec, (f"decorator @{name or '<expr>'} prevents "
                            f"dy2static conversion (stripping it would "
                            f"change behavior)")


@register(
    "PDT108", "eager-optimizer-loop", Severity.NOTE, "ast", scope="eager",
    example="""
import paddle_tpu as paddle

def train(model, opt, batches):
    for x, y in batches:
        loss = ((model(x) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
""",
    near_miss="""
import paddle_tpu as paddle

@paddle.jit.to_static
def train_step(model, opt, x, y):
    loss = ((model(x) - y) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()
    return loss
""")
def check_eager_optimizer_loop(fndef, ctx):
    """A training loop (``backward()`` + ``.step()`` in the same loop
    body) in a function NOT under ``jit.to_static``: every iteration
    dispatches the whole step eagerly — the optimizer update alone is
    O(params) host dispatches on the per-param path and still O(buckets)
    on the fused path, vs ZERO once the step is captured (and one
    launch per K steps with ``Model.fit(window=K)`` / ``WindowRunner``).
    Note-level advice, not an error."""
    for node in _walk_fn(fndef):
        if not isinstance(node, (ast.For, ast.While)):
            continue
        has_backward = False
        step_node = None
        # own-scope walk of the loop body: nested defs are linted as
        # their own scope (same contract as _walk_fn), so a closure
        # merely DEFINED in the loop doesn't flag the outer function
        stack = [node]
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.extend(ast.iter_child_nodes(sub))
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute):
                if sub.func.attr == "backward":
                    has_backward = True
                elif sub.func.attr in ("step", "minimize") and \
                        step_node is None:
                    step_node = sub
        if has_backward and step_node is not None:
            yield step_node, (
                "optimizer step inside an eager Python loop: every "
                "batch pays per-step host dispatch — wrap the train "
                "step in @paddle.jit.to_static (or use "
                "Model.fit(window=K)) so the loop body compiles to one "
                "program")


# constructor kwargs that bound a serving engine's overload behavior
# (inference/engine.py): any one of them makes PDT109 stand down.
# dispatch_retries is deliberately NOT here — it bounds transient
# retry, not queue growth or request lifetime.
_ENGINE_BOUND_KWARGS = {"max_queue", "queue_policy",
                        "default_deadline_ms"}


@register(
    "PDT109", "unbounded-serving-run", Severity.NOTE, "ast",
    scope="eager",
    example="""
import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    eng = ContinuousBatchingEngine(model, max_slots=4)
    for p in prompts:
        eng.add_request(p, 32)
    return eng.run()
""",
    near_miss="""
import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    eng = ContinuousBatchingEngine(model, max_slots=4, max_queue=64,
                                   queue_policy="reject")
    for p in prompts:
        eng.add_request(p, 32)
    return eng.run()
""")
def check_unbounded_serving_run(fndef, ctx):
    """``ContinuousBatchingEngine.run()`` on an engine constructed with
    no overload policy (no ``max_queue``/``queue_policy`` bound, no
    ``default_deadline_ms`` TTL): fine in the lab, but under real
    traffic an unbounded queue plus deadline-free requests means
    overload shows up as unbounded memory and latency instead of
    rejections/timeouts.  Configure the bounds (or the ``serving_*``
    flags in ``core/state.py``).  Note-level advice, not an error."""
    # pass 1: every assignment to a name, in source order — a name is
    # suspect at a .run() site iff its latest PRECEDING assignment is
    # an engine constructed without any bound (so rebinding the name
    # to anything else clears it; _walk_fn order is not source order)
    assigns: dict[str, list[tuple[tuple[int, int], bool]]] = {}
    for node in _walk_fn(fndef):
        if isinstance(node, ast.Assign):
            is_engine = (isinstance(node.value, ast.Call)
                         and (_dotted(node.value.func) or "")
                         .split(".")[-1] == "ContinuousBatchingEngine")
            suspect = is_engine and not any(
                kw.arg in _ENGINE_BOUND_KWARGS
                for kw in node.value.keywords)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    assigns.setdefault(tgt.id, []).append(
                        ((node.lineno, node.col_offset), suspect))
    for hist in assigns.values():
        hist.sort()

    def _unbounded_at(name, pos):
        last = None
        for apos, suspect in assigns.get(name, ()):
            if apos > pos:
                break
            last = suspect
        return bool(last)

    for node in _walk_fn(fndef):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute) \
                or node.func.attr != "run":
            continue
        base = node.func.value
        chained = (isinstance(base, ast.Call)
                   and (_dotted(base.func) or "").split(".")[-1]
                   == "ContinuousBatchingEngine"
                   and not any(kw.arg in _ENGINE_BOUND_KWARGS
                               for kw in base.keywords))
        named = (isinstance(base, ast.Name)
                 and _unbounded_at(base.id, (node.lineno,
                                             node.col_offset)))
        if chained or named:
            yield node, (
                "ContinuousBatchingEngine.run() with no overload "
                "policy configured: pass max_queue/queue_policy "
                "and/or default_deadline_ms (or set the serving_* "
                "flags) so heavy traffic degrades to rejections/"
                "timeouts instead of unbounded queues")


@register(
    "PDT111", "dequant-then-matmul", Severity.NOTE, "ast", scope="any",
    example="""
from paddle_tpu.quantization import weight_dequantize

def serve(x, qw, scale):
    w = weight_dequantize(qw, scale)
    return x @ w
""",
    near_miss="""
from paddle_tpu.quantization import (weight_dequantize,
                                     weight_only_linear)

def serve(x, qw, scale):
    probe = weight_dequantize(qw, scale)   # inspected, never matmul'd
    shape = probe.shape
    return weight_only_linear(x, qw, scale), shape
""")
def check_dequant_then_matmul(fndef, ctx):
    """``weight_dequantize`` whose result feeds a matmul (``@``,
    ``matmul(...)``, ``linear(...)``): the dequantized weight is
    materialized at FLOAT width before the matmul reads it — eagerly
    that is a full extra HBM round-trip at 4x the quantized bytes, and
    even under jit it gambles on XLA fusing the pair.
    ``quantization.weight_only_linear`` (the Pallas fused
    dequant-matmul, ``ops/pallas/quant_matmul.py``) reads the weights
    at int8 width and applies the scale after the K reduction.
    Note-level advice, not an error."""
    # source-position-aware name tracking (the PDT109 hardening): a
    # name is a dequant result at a use site iff its latest PRECEDING
    # assignment was a weight_dequantize call — rebinding clears it,
    # and a later dequant assignment does not taint earlier uses
    assigns: dict[str, list[tuple[tuple[int, int], bool]]] = {}
    for node in _walk_fn(fndef):
        if isinstance(node, ast.Assign):
            is_dq = (isinstance(node.value, ast.Call)
                     and (_dotted(node.value.func) or "")
                     .split(".")[-1] == "weight_dequantize")
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    assigns.setdefault(tgt.id, []).append(
                        ((node.lineno, node.col_offset), is_dq))
    for hist in assigns.values():
        hist.sort()

    def _is_dequant(arg, pos):
        if isinstance(arg, ast.Name):
            last = None
            for apos, is_dq in assigns.get(arg.id, ()):
                if apos >= pos:
                    break
                last = is_dq
            return bool(last)
        return (isinstance(arg, ast.Call)
                and (_dotted(arg.func) or "").split(".")[-1]
                == "weight_dequantize")

    msg = ("matmul over a weight_dequantize result materializes the "
           "float weights in HBM before the matmul re-reads them; "
           "weight_only_linear fuses the dequant into the matmul at "
           "int8 read width")
    for node in _walk_fn(fndef):
        if not isinstance(node, (ast.BinOp, ast.Call)):
            continue
        pos = (node.lineno, node.col_offset)
        if isinstance(node, ast.BinOp) and isinstance(node.op,
                                                      ast.MatMult):
            if _is_dequant(node.left, pos) or _is_dequant(node.right,
                                                          pos):
                yield node, msg
        elif isinstance(node, ast.Call) \
                and (_dotted(node.func) or "").split(".")[-1] \
                in ("matmul", "linear") \
                and any(_is_dequant(a, pos) for a in node.args
                        + [kw.value for kw in node.keywords]):
            yield node, msg


# call names that read as "logging": the sink whose arguments PDT112
# scans for device->host syncs. Bare names take only the unambiguous
# spellings; dotted chains match logger METHOD names on the last part
# (logger.info / self.log.debug) — deliberately NOT "log", which as an
# attribute is overwhelmingly math (math.log/np.log/jnp.log), where
# the sync is a real data dependency the check must not flag.
_LOG_SINK_BARE = {"print", "log"}
_LOG_SINK_METHODS = {"info", "debug", "warning", "error", "critical",
                     "exception"}
_HOST_SYNC_LOOP_METHODS = {"item", "numpy", "tolist"}


def _host_sync_desc(node):
    """The device->host sync expression a log-call argument performs
    (``float()`` / ``.item()`` / ``.numpy()`` / ``.tolist()``), or
    None.  Shared by PDT112 and PDT115 so the two checks can never
    disagree on what counts as a sync."""
    if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute) \
                and f.attr in _HOST_SYNC_LOOP_METHODS \
                and not node.args and not node.keywords:
            return f".{f.attr}()"
        if isinstance(f, ast.Name) and f.id == "float" \
                and len(node.args) == 1 and not node.keywords:
            return "float()"
    return None


@register(
    "PDT112", "host-sync-in-loop", Severity.NOTE, "ast", scope="eager",
    example="""
import paddle_tpu as paddle

def train(model, batches):
    for x in batches:
        loss = model(x).mean()
        print("loss:", float(loss))
""",
    near_miss="""
import math
import paddle_tpu as paddle

def train(model, batches):
    for x in batches:
        loss = model(x).mean()
        scale = math.log(float(loss))     # math, not logging
        if float(loss) < 0.1:
            break
""")
def check_host_sync_in_loop(fndef, ctx):
    """``float(x)`` / ``x.item()`` / ``x.numpy()`` / ``x.tolist()``
    feeding a logging call (``print`` / ``log.info`` / ...) inside a
    training or serving loop body: each one blocks the host on a
    device->host transfer EVERY iteration, purely to print a number —
    on a network-attached TPU that is a full round-trip per step.
    ``paddle_tpu.observability`` gauges read LAZILY (the value is
    fetched at snapshot/render time, not in the loop), so telemetry
    costs the loop nothing; syncs that feed control flow (early
    stopping on ``float(loss)``) are real data dependencies and are
    not flagged.  Note-level advice, not an error."""
    _sync_desc = _host_sync_desc

    for loop in _walk_fn(fndef):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        # own-scope walk of the loop body (nested defs lint themselves)
        stack = list(ast.iter_child_nodes(loop))
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.extend(ast.iter_child_nodes(sub))
            if not isinstance(sub, ast.Call):
                continue
            fname = (_dotted(sub.func) or "").split(".")[-1]
            is_sink = (fname in _LOG_SINK_BARE
                       if isinstance(sub.func, ast.Name)
                       else fname in _LOG_SINK_METHODS)
            if not is_sink:
                continue
            for arg in sub.args + [kw.value for kw in sub.keywords]:
                for inner in ast.walk(arg):
                    desc = _sync_desc(inner)
                    if desc is not None:
                        yield inner, (
                            f"{desc} inside a loop body feeds only "
                            f"{fname}(): that is one device->host sync "
                            f"per iteration spent on logging — record "
                            f"into a paddle_tpu.observability gauge/"
                            f"histogram instead (gauges read lazily at "
                            f"snapshot time, so the loop pays nothing)")
                        break  # one finding per log-call argument


@register(
    "PDT113", "greedy-spec-sampling-mismatch", Severity.NOTE, "ast",
    scope="eager",
    example="""
import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    eng = ContinuousBatchingEngine(model, max_slots=8, spec_decode=True,
                                   spec_temperature=0.8)
    for p in prompts:
        eng.add_request(p, 32)
    return eng.run()
""",
    near_miss="""
import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    # rejection sampling keeps sampled speculative output lossless
    eng = ContinuousBatchingEngine(model, max_slots=8, spec_decode=True,
                                   spec_temperature=0.8,
                                   spec_rejection_sampling=True)
    for p in prompts:
        eng.add_request(p, 32)
    greedy = ContinuousBatchingEngine(model, max_slots=8,
                                      spec_decode=True)  # greedy: exact
    return eng.run()
""")
def check_greedy_spec_sampling_mismatch(fndef, ctx):
    """A serving engine constructed with ``spec_decode`` on and a
    non-greedy sampler (``spec_temperature > 0``) but WITHOUT
    ``spec_rejection_sampling``: token-equality acceptance against
    sampled target tokens skews the output distribution toward the
    proposer (a draft is kept whenever the sampler happens to agree,
    so proposer-favored continuations are over-represented), which
    silently changes what the model says, not just how fast.  Greedy
    speculative decoding (``spec_temperature = 0``, the default) is
    exact by construction; sampled speculative decoding is exact only
    under the rejection-sampling rule — set
    ``spec_rejection_sampling=True`` (or the
    ``serving_spec_rejection_sampling`` flag) or drop the
    temperature.  Note-level advice, not an error."""

    def _truthy(node):
        return isinstance(node, ast.Constant) and bool(node.value)

    for node in _walk_fn(fndef):
        if not isinstance(node, ast.Call) \
                or (_dotted(node.func) or "").split(".")[-1] \
                != "ContinuousBatchingEngine":
            continue
        kws = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        if _truthy(kws.get("spec_decode")) \
                and _truthy(kws.get("spec_temperature")) \
                and not _truthy(kws.get("spec_rejection_sampling")):
            yield node, (
                "spec_decode with spec_temperature but no "
                "spec_rejection_sampling: greedy token-equality "
                "acceptance under a sampling temperature biases "
                "output toward the proposer — enable "
                "spec_rejection_sampling (lossless speculative "
                "sampling) or decode greedily")


# constant values that disable the engine's prefix cache — the string
# spellings are the engine's case-insensitive parse set
_PREFIX_CACHE_OFF = (False, 0) + PREFIX_CACHE_OFF_SPELLINGS


def _prefix_cache_off(node) -> bool:
    if not isinstance(node, ast.Constant):
        return False
    v = node.value
    if isinstance(v, str):
        v = v.lower()
    return v in _PREFIX_CACHE_OFF


@register(
    "PDT110", "prefix-cache-off-under-load", Severity.NOTE, "ast",
    scope="eager",
    example="""
import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    eng = ContinuousBatchingEngine(model, max_slots=8, max_queue=64,
                                   queue_policy="reject",
                                   prefix_cache=False)
    for p in prompts:
        eng.add_request(p, 32)
    return eng.run()
""",
    near_miss="""
import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    # overload-bounded engine keeps the prefix cache (default on)
    eng = ContinuousBatchingEngine(model, max_slots=8, max_queue=64,
                                   queue_policy="reject")
    for p in prompts:
        eng.add_request(p, 32)
    lab = ContinuousBatchingEngine(model, max_slots=8,
                                   prefix_cache=False)  # lab parity rig
    return eng.run()
""")
def check_prefix_cache_off_under_load(fndef, ctx):
    """A serving engine constructed with the prefix cache explicitly
    DISABLED (``prefix_cache=False``/``'off'``) while overload knobs
    (``max_queue``/``queue_policy``/``default_deadline_ms``) are set:
    the high-traffic configuration those knobs exist for is exactly the
    one that most benefits from cross-request prefix caching — shared
    system prompts stop re-prefilling and preempt-requeue stops
    recomputing work the engine already did, at zero output difference
    (cache hits are bitwise-identical).  Disabling it is legitimate for
    parity rigs and memory-ceiling experiments, hence note-level
    advice, not an error."""
    for node in _walk_fn(fndef):
        if not isinstance(node, ast.Call) \
                or (_dotted(node.func) or "").split(".")[-1] \
                != "ContinuousBatchingEngine":
            continue
        kws = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        if _prefix_cache_off(kws.get("prefix_cache")) \
                and any(k in _ENGINE_BOUND_KWARGS for k in kws):
            yield node, (
                "engine bounded for overload (max_queue/queue_policy/"
                "default_deadline_ms) but built with "
                "prefix_cache=False: high-traffic serving is where the "
                "KV prefix cache pays most (shared prompts skip "
                "re-prefill; preempted requests restore instead of "
                "recomputing) and hits are bitwise-identical — drop "
                "the override or set serving_prefix_cache")


@register(
    "PDT114", "serialized-grad-sync", Severity.NOTE, "ast",
    scope="eager",
    example="""
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

def train(model, opt, batches):
    dp = dist.DataParallel(model)
    for x, y in batches:
        loss = ((dp(x) - y) ** 2).mean()
        loss.backward()
        dp.apply_collective_grads()
        opt.step()
        opt.clear_grad()
""",
    near_miss="""
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

def train(model, opt, batches):
    # overlap scheduler: bucket collectives dispatch DURING backward,
    # apply_collective_grads only drains the pending results
    dp = dist.DataParallel(model, overlap_grad_sync=True)
    for x, y in batches:
        loss = ((dp(x) - y) ** 2).mean()
        loss.backward()
        dp.apply_collective_grads()
        opt.step()
        opt.clear_grad()
""")
def check_serialized_grad_sync(fndef, ctx):
    """An explicit blocking gradient all-reduce
    (``apply_collective_grads()`` / ``all_reduce(...grad...)``) between
    ``backward()`` and ``step()`` in an eager train loop: every
    collective waits for the WHOLE backward and the step waits for
    every collective, so communication serializes with compute. The
    bucketed overlap scheduler (``DataParallel(...,
    overlap_grad_sync=True)`` or the ``dp_overlap_grad_sync`` flag)
    dispatches one psum-mean per size-capped bucket as each bucket's
    grads finalize during the backward walk — bitwise-identical
    results, collectives hidden under the remaining backward compute
    (``train.overlap_frac`` in the observability registry shows how
    much). Note-level advice, not an error."""

    def _overlap_enabled():
        # a DataParallel(...) built anywhere in this function with a
        # truthy overlap_grad_sync already overlaps: stand down
        for node in _walk_fn(fndef):
            if isinstance(node, ast.Call) \
                    and (_dotted(node.func) or "").split(".")[-1] \
                    == "DataParallel":
                for kw in node.keywords:
                    if kw.arg == "overlap_grad_sync" \
                            and isinstance(kw.value, ast.Constant) \
                            and bool(kw.value.value):
                        return True
        return False

    if _overlap_enabled():
        return
    for node in _walk_fn(fndef):
        if not isinstance(node, (ast.For, ast.While)):
            continue
        has_backward = False
        sync_node = None
        has_step = False
        # own-scope walk (PDT108 contract): nested defs lint themselves
        stack = [node]
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.extend(ast.iter_child_nodes(sub))
            if not (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)):
                continue
            attr = sub.func.attr
            if attr == "backward":
                has_backward = True
            elif attr == "apply_collective_grads":
                sync_node = sync_node or sub
            elif attr == "all_reduce" and sub.args:
                # all_reduce(p.grad ...) — the hand-rolled per-tensor
                # spelling of the same serialized sync
                a0 = sub.args[0]
                if isinstance(a0, ast.Attribute) and a0.attr == "grad":
                    sync_node = sync_node or sub
            elif attr in ("step", "minimize"):
                has_step = True
        if has_backward and sync_node is not None and has_step:
            yield sync_node, (
                "blocking grad all-reduce between backward() and "
                "step(): the collectives serialize after the whole "
                "backward — construct DataParallel with "
                "overlap_grad_sync=True (or set dp_overlap_grad_sync) "
                "so bucket collectives dispatch as grads finalize "
                "during backward and overlap the remaining compute; "
                "results are bitwise-identical")


# attribute/call spellings that read as "this rank's index" in a rank
# conditional (dist.get_rank() == 0, env.local_rank == 0, hcg rank
# getters) — the guard PDT115 looks for around per-rank logging
_RANK_CALL_NAMES = {"get_rank", "get_local_rank", "get_data_parallel_rank",
                    "get_model_parallel_rank", "get_stage_id"}
_RANK_ATTR_NAMES = {"rank", "local_rank"}


def _is_rank_conditional(test) -> bool:
    """True when an ``if`` test reads this process's rank: a call like
    ``dist.get_rank()`` or an attribute like ``env.local_rank``
    anywhere in the expression."""
    for sub in ast.walk(test):
        if isinstance(sub, ast.Call):
            name = (_dotted(sub.func) or "").split(".")[-1]
            if name in _RANK_CALL_NAMES:
                return True
        elif isinstance(sub, ast.Attribute) \
                and sub.attr in _RANK_ATTR_NAMES:
            return True
    return False


@register(
    "PDT115", "per-rank-metrics-leak", Severity.NOTE, "ast",
    scope="eager",
    example="""
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

def train(model, batches):
    for x in batches:
        loss = model(x).mean()
        if dist.get_rank() == 0:
            print("rank0 loss:", float(loss))
""",
    near_miss="""
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

def train(model, batches):
    for step, x in enumerate(batches):
        loss = model(x).mean()
        if dist.get_rank() == 0:
            print("step", step)       # python scalar: no device sync
""")
def check_per_rank_metrics_leak(fndef, ctx):
    """``float(x)`` / ``.item()`` / ``.numpy()`` / ``.tolist()``
    feeding a logging call inside a RANK-CONDITIONAL block
    (``if dist.get_rank() == 0: print(float(loss))``) of a distributed
    loop body: beyond PDT112's per-iteration device->host sync, this
    pattern structurally LOSES the fleet view — only the printing
    rank's value ever surfaces, so the cross-rank skew that the
    conditional was hiding (the straggler, its phase) is exactly what
    never gets logged.  Record into registry gauges/histograms on
    EVERY rank (lazy reads, no loop cost) and call
    ``observability.fleet_snapshot()`` for the merged view with
    per-rank ``step_ms`` skew and slowest-rank attribution instead.
    Note-level advice, not an error."""
    for loop in _walk_fn(fndef):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        # own-scope walk (PDT108 contract): nested defs lint themselves
        stack = list(ast.iter_child_nodes(loop))
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.extend(ast.iter_child_nodes(sub))
            if not (isinstance(sub, ast.If)
                    and _is_rank_conditional(sub.test)):
                continue
            for inner in sub.body:
                for call in ast.walk(inner):
                    if not isinstance(call, ast.Call):
                        continue
                    fname = (_dotted(call.func) or "").split(".")[-1]
                    is_sink = (fname in _LOG_SINK_BARE
                               if isinstance(call.func, ast.Name)
                               else fname in _LOG_SINK_METHODS)
                    if not is_sink:
                        continue
                    for arg in call.args + [kw.value
                                            for kw in call.keywords]:
                        hit = next(
                            (n for n in ast.walk(arg)
                             if _host_sync_desc(n) is not None), None)
                        if hit is not None:
                            yield hit, (
                                f"{_host_sync_desc(hit)} logged only "
                                f"on one rank inside a distributed "
                                f"loop: the synced value costs a "
                                f"device round-trip per iteration AND "
                                f"every other rank's number is thrown "
                                f"away — record registry gauges/"
                                f"histograms on all ranks (lazy reads) "
                                f"and merge with observability."
                                f"fleet_snapshot(), which also derives "
                                f"per-rank step_ms skew and "
                                f"slowest-rank attribution")
                            break   # one finding per log call


# constructor/call names that put a multi-device mesh "in scope" for
# PDT116: a serving engine built single-device right next to one of
# these is almost always an oversight, not a lab rig
_MESH_EVIDENCE_CALLS = {"ProcessMesh", "Mesh", "device_count"}


@register(
    "PDT116", "single-device-engine-on-mesh", Severity.NOTE, "ast",
    scope="eager",
    example="""
import jax
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    mesh = dist.ProcessMesh(np.arange(jax.device_count()), ["tp"])
    eng = ContinuousBatchingEngine(model, max_slots=8)
    for p in prompts:
        eng.add_request(p, 32)
    return eng.run()
""",
    near_miss="""
import jax
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    mesh = dist.ProcessMesh(np.arange(jax.device_count()), ["tp"])
    eng = ContinuousBatchingEngine(model, max_slots=8, mesh=mesh)
    for p in prompts:
        eng.add_request(p, 32)
    return eng.run()
""")
def check_single_device_engine_on_mesh(fndef, ctx):
    """A serving engine constructed WITHOUT ``mesh=``/``tp_axis=`` in
    a function that is visibly mesh-aware (it builds a
    ``ProcessMesh``/``Mesh`` or consults ``jax.device_count()``): the
    engine will compile its two serving programs on ONE device while
    the rest of the mesh idles — weights that could column/row-split
    over the tensor-parallel axis (one psum at the attention output
    and the MLP reduce; KV pools sharded by kv-head) are replicated
    instead, capping both model size and decode throughput at a
    single chip.  Pass ``mesh=``/``tp_axis=`` (or set the
    ``serving_tp`` flag) — greedy outputs are token-identical to the
    single-device engine, so sharding is free at the output level.
    Single-device parity rigs are legitimate, hence note-level
    advice, not an error."""
    has_mesh_evidence = any(
        isinstance(node, ast.Call)
        and (_dotted(node.func) or "").split(".")[-1]
        in _MESH_EVIDENCE_CALLS
        for node in _walk_fn(fndef))
    if not has_mesh_evidence:
        return
    for node in _walk_fn(fndef):
        if not isinstance(node, ast.Call) \
                or (_dotted(node.func) or "").split(".")[-1] \
                != "ContinuousBatchingEngine":
            continue
        kws = {kw.arg for kw in node.keywords if kw.arg}
        if "mesh" not in kws and "tp_axis" not in kws:
            yield node, (
                "serving engine built single-device while a "
                "multi-device mesh is in scope (ProcessMesh/Mesh/"
                "device_count in this function): pass mesh=/tp_axis= "
                "so the serving programs shard over the "
                "tensor-parallel axis — greedy outputs stay "
                "token-identical and decode stops being capped at "
                "one chip")


# overload knobs that prove an engine expects real traffic, and the
# judgment-layer kwargs that answer them — PDT117 fires on the first
# set without the second.  dispatch_retries/prefix_cache are absent
# from the trigger set deliberately: they tune mechanics, not load.
_ENGINE_OVERLOAD_KWARGS = {"max_queue", "queue_policy",
                           "default_deadline_ms"}
_ENGINE_GUARD_KWARGS = {"slo", "watchdog_ms"}


@register(
    "PDT117", "no-slo-guard-under-load", Severity.NOTE, "ast",
    scope="eager",
    example="""
import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    eng = ContinuousBatchingEngine(model, max_slots=8, max_queue=64,
                                   queue_policy="reject",
                                   default_deadline_ms=500.0)
    for p in prompts:
        eng.add_request(p, 32)
    return eng.run()
""",
    near_miss="""
import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    eng = ContinuousBatchingEngine(model, max_slots=8, max_queue=64,
                                   queue_policy="reject",
                                   default_deadline_ms=500.0,
                                   slo="ttft_p95_ms=500,goodput=0.99",
                                   watchdog_ms=2000.0)
    for p in prompts:
        eng.add_request(p, 32)
    return eng.run()
""")
def check_no_slo_guard_under_load(fndef, ctx):
    """A serving engine constructed WITH overload knobs
    (``max_queue``/``queue_policy``/``default_deadline_ms`` — this
    engine clearly expects heavy traffic) but with NO judgment layer:
    no SLO spec (``slo=`` / ``serving_slo`` flag) and no stall
    watchdog (``watchdog_ms`` / ``watchdog_stall_ms`` flag).  The
    overload policies will shed and preempt correctly, but nothing
    evaluates the latency histograms against objectives (a TTFT p95
    burning its error budget is invisible until users complain) and a
    hung dispatch hangs the caller forever instead of surfacing a
    coded ``EngineStallError`` with thread stacks in a flight record.
    Arm at least one of ``slo=``/``watchdog_ms=``.  Note-level
    advice, not an error."""
    for node in _walk_fn(fndef):
        if not isinstance(node, ast.Call) \
                or (_dotted(node.func) or "").split(".")[-1] \
                != "ContinuousBatchingEngine":
            continue
        kws = {kw.arg for kw in node.keywords if kw.arg}
        if kws & _ENGINE_OVERLOAD_KWARGS \
                and not kws & _ENGINE_GUARD_KWARGS:
            yield node, (
                "engine has overload knobs (max_queue/queue_policy/"
                "default_deadline_ms) but no SLO spec or watchdog "
                "armed: pass slo= (or the serving_slo flag) so the "
                "TTFT/TPOT/goodput histograms are judged against "
                "objectives with burn-rate alerting, and watchdog_ms= "
                "(or watchdog_stall_ms) so a hung dispatch dumps "
                "stacks and fails coded instead of hanging")


# constructs that prove a TRAINING function is fleet-aware (PDT118):
# mesh/world evidence as for PDT116, plus the distributed-launch world
# probes a multi-host fit reads before sharding its data
_FLEET_EVIDENCE_CALLS = _MESH_EVIDENCE_CALLS | {
    "get_world_size", "init_parallel_env"}
# recovery arming that answers it: the elastic supervisor (buddy
# snapshots + collective watchdog + detector-driven resume) or at
# minimum the preemption hook (checkpoint-at-boundary + clean exit).
# ``install`` is matched as the dotted suffix ``preempt.install`` —
# a bare last-component match would let any unrelated ``x.install()``
# silently suppress the diagnostic
_FIT_GUARD_CALLS = {"FleetSupervisor"}


def _arms_fit_guard(dotted):
    return dotted.split(".")[-1] in _FIT_GUARD_CALLS \
        or dotted == "preempt.install" \
        or dotted.endswith(".preempt.install")


@register(
    "PDT118", "unsupervised-multihost-fit", Severity.NOTE, "ast",
    scope="eager",
    example="""
import jax
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

def train(model, data):
    world = jax.device_count()
    mesh = dist.ProcessMesh(np.arange(world), ["dp"])
    for epoch in range(10):
        model.fit(data, batch_size=32, epochs=1)
""",
    near_miss="""
import jax
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.resilience import preempt

def train(model, data):
    world = jax.device_count()
    mesh = dist.ProcessMesh(np.arange(world), ["dp"])
    with preempt.install():
        for epoch in range(10):
            model.fit(data, batch_size=32, epochs=1,
                      save_dir="ckpt", resume=True)
""")
def check_unsupervised_multihost_fit(fndef, ctx):
    """``Model.fit`` in a function that is visibly fleet-aware (it
    builds a ``ProcessMesh``/``Mesh`` or consults ``device_count``/
    ``get_world_size``/``init_parallel_env``) with NEITHER
    ``resilience.FleetSupervisor`` NOR ``preempt.install()`` armed: at
    fleet scale the dominant availability cost is the recovery, and an
    unarmed fit pays it in full — a single dead rank hangs every
    survivor inside the gradient psum (no collective watchdog, so no
    coded ``CollectiveTimeoutError``), and the only way back is a full
    restart from on-disk checkpoints instead of a buddy in-memory
    restore at the last snapshot boundary.  Wrap the loop in
    ``FleetSupervisor.fit`` (buddy snapshots + watchdog + elastic
    resume) or at minimum arm ``preempt.install()`` so preemptions
    checkpoint at a step boundary.  Single-device rigs are legitimate,
    hence note-level advice."""
    has_fleet_evidence = any(
        isinstance(node, ast.Call)
        and (_dotted(node.func) or "").split(".")[-1]
        in _FLEET_EVIDENCE_CALLS
        for node in _walk_fn(fndef))
    if not has_fleet_evidence:
        return
    armed = any(
        isinstance(node, ast.Call)
        and _arms_fit_guard(_dotted(node.func) or "")
        for node in _walk_fn(fndef))
    if armed:
        return
    for node in _walk_fn(fndef):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute) \
                or node.func.attr != "fit":
            continue
        yield node, (
            "Model.fit in a fleet-aware function (ProcessMesh/Mesh/"
            "device_count/get_world_size in scope) with neither "
            "FleetSupervisor nor preempt.install() armed: a dead rank "
            "hangs every survivor in the gradient psum and recovery "
            "means a full on-disk restart — arm resilience."
            "FleetSupervisor (buddy in-memory snapshots, collective "
            "watchdog PDT-E021, detector-driven resume) or at least "
            "preempt.install() for checkpoint-at-boundary exits")


# replica-pool constructors PDT119 counts, and the front-end that
# proves the pool is routed.  RpcReplica is deliberately included in
# the pool set: N hand-held rpc proxies without a router have the
# same failure mode as N hand-held engines.
_REPLICA_POOL_CALLS = {"ContinuousBatchingEngine", "DisaggServer",
                       "RpcReplica"}
_ROUTER_CALLS = {"FleetRouter"}


@register(
    "PDT119", "unrouted-replica-pool", Severity.NOTE, "ast",
    scope="eager",
    example="""
import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine

def serve(model, prompts):
    engines = [ContinuousBatchingEngine(model, max_slots=8),
               ContinuousBatchingEngine(model, max_slots=8)]
    for i, p in enumerate(prompts):
        engines[i % 2].add_request(p, 32)
    return [e.run() for e in engines]
""",
    near_miss="""
import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine, FleetRouter

def serve(model, prompts):
    router = FleetRouter(replicas=[
        ContinuousBatchingEngine(model, max_slots=8),
        ContinuousBatchingEngine(model, max_slots=8)])
    for p in prompts:
        router.add_request(p, 32)
    return router.run()
""")
def check_unrouted_replica_pool(fndef, ctx):
    """TWO OR MORE serving replicas (``ContinuousBatchingEngine`` /
    ``DisaggServer`` / ``RpcReplica``) constructed in one function
    with no ``FleetRouter`` in sight: the pool is being spread by
    hand.  Hand-spreading gets none of the fleet layer — no
    prefix-cache-aware placement (shared-prefix traffic scatters, so
    every replica re-prefills what another already cached), no
    tenant fair share, and above all no failure handling: a replica
    that dies mid-decode takes its queued and in-flight requests with
    it, where the router would requeue them to survivors
    bitwise-identically under one coded PDT-E024 flight record.
    Wrap the pool: ``FleetRouter(replicas=[...])`` — or pass
    ``replicas=N`` and let the router build them.  Note-level advice;
    deliberately independent pools (A/B harnesses, test rigs) are
    legitimate."""
    if any(isinstance(node, ast.Call)
           and (_dotted(node.func) or "").split(".")[-1]
           in _ROUTER_CALLS
           for node in _walk_fn(fndef)):
        return
    seen = 0
    for node in _walk_fn(fndef):
        if not isinstance(node, ast.Call) \
                or (_dotted(node.func) or "").split(".")[-1] \
                not in _REPLICA_POOL_CALLS:
            continue
        seen += 1
        if seen == 2:
            yield node, (
                "two or more serving replicas built here with no "
                "FleetRouter: hand-spread pools lose cache-aware "
                "placement, tenant fair share, and dead-replica "
                "requeue (a replica loss drops its in-flight "
                "requests instead of re-serving them bitwise from "
                "survivors under a coded PDT-E024 record) — wrap "
                "the pool in FleetRouter(replicas=[...])")


# batch-staging calls a custom train loop pays synchronously per step:
# to_tensor / Tensor() host->device conversion and jax device_put. The
# .numpy() direction (device->host readback of the loss) already has
# its own coded finding (PDT101 inside jit); here it marks the loop as
# feeding the device from host data, same as the converters.
_INPUT_STAGE_CALLS = {"to_tensor", "device_put", "Tensor", "asarray"}


def _loop_stages_and_steps(loop):
    """Does ONE loop body both stage host batches and run a train
    step?  Staging = a conversion call from ``_INPUT_STAGE_CALLS``;
    a step = a ``.backward()`` call (the unambiguous train marker) or
    a ``train_batch``/``step`` method call."""
    stages = steps = False
    for node in ast.walk(loop):
        if not isinstance(node, ast.Call):
            continue
        name = (_dotted(node.func) or "").split(".")[-1]
        if name in _INPUT_STAGE_CALLS:
            stages = True
        elif name in ("backward", "train_batch"):
            steps = True
        if stages and steps:
            return True
    return False


@register(
    "PDT121", "eager-input-feed", Severity.NOTE, "ast",
    scope="eager",
    example="""
import paddle_tpu as paddle

def train(model, opt, loader, loss_fn):
    for batch in loader:
        ids = paddle.to_tensor(batch[0])
        lab = paddle.to_tensor(batch[1])
        loss = loss_fn(model(ids), lab)
        loss.backward()
        opt.step()
        opt.clear_grad()
""",
    near_miss="""
import paddle_tpu as paddle

def train(model, opt, loader, loss_fn):
    staged = None
    for batch in loader:
        ids, lab = staged if staged else (paddle.to_tensor(batch[0]),
                                          paddle.to_tensor(batch[1]))
        staged = None  # prefetch: next batch staged under the step
        loss = loss_fn(model(ids), lab)
        loss.backward()
        opt.step()
        opt.clear_grad()
""")
def check_eager_input_feed(fndef, ctx):
    """A hand-written train loop that stages its batches SYNCHRONOUSLY
    inside the step loop — ``to_tensor``/``device_put`` conversion in
    the same loop body as the ``backward()`` — with no prefetch knob
    anywhere in scope.  Every step then serializes host->device
    transfer with device compute: the chip idles for the full staging
    time, per step.  ``hapi.Model.fit`` double-buffers this for free
    (the ``train_prefetch`` flag: batch N+1 stages while step N is in
    flight, bitwise-identical loss trajectory, the wait surfaces as
    ``train.input_wait_ms``); custom loops can do the same by staging
    the next batch between the step's dispatch and its loss readback.
    Note-level advice: profile-time rigs that want the synchronous
    cost visible are legitimate.  Suppressed when anything named
    ``*prefetch*`` is in scope (a knob or a hand-rolled feed) or the
    loop is already double-buffered through a ``staged``/``queue``
    variable the loop consumes."""
    src_names = set()
    for node in _walk_fn(fndef):
        if isinstance(node, ast.Name):
            src_names.add(node.id.lower())
        elif isinstance(node, ast.Attribute):
            src_names.add(node.attr.lower())
        elif isinstance(node, ast.Constant) and isinstance(node.value,
                                                          str):
            src_names.add(node.value.lower())
    if any("prefetch" in n or n == "staged" for n in src_names):
        return
    for node in _walk_fn(fndef):
        if isinstance(node, (ast.For, ast.While)) \
                and _loop_stages_and_steps(node):
            yield node, (
                "batches are staged synchronously inside the step "
                "loop (to_tensor/device_put in the same body as "
                "backward()): host->device transfer serializes with "
                "device compute every step — use hapi.Model.fit's "
                "train_prefetch double-buffering (bitwise-identical "
                "loss trajectory; the residual wait surfaces as "
                "train.input_wait_ms), or stage batch N+1 between "
                "the step's dispatch and its loss readback")
            return


# router kwargs that prove the fleet is judged on latency: deadlines
# tick and SLOs burn while a cold drain waits out tail decodes
_ROUTER_SLO_KWARGS = {"fleet_slo", "default_deadline_ms",
                      "scalein_hold_s"}


def _migration_off_or_absent(call) -> bool:
    for kw in call.keywords:
        if kw.arg == "migration":
            v = kw.value
            if not isinstance(v, ast.Constant):
                return False      # computed value: can't prove it's off
            # None defers to the serving_migration flag default: off
            return v.value in (None, False, 0)
    return True                   # absent: serving_migration defaults off


@register(
    "PDT122", "cold-drain-under-load", Severity.NOTE, "ast",
    scope="eager",
    example="""
import paddle_tpu as paddle
from paddle_tpu.inference import FleetRouter

def serve_fleet(model, prompts):
    r = FleetRouter(model, replicas=4, standby=1,
                    fleet_slo="queue_p95_ms=200,goodput=0.99",
                    default_deadline_ms=500.0,
                    scalein_hold_s=30.0)
    for p in prompts:
        r.add_request(p, 32)
    return r.run()
""",
    near_miss="""
import paddle_tpu as paddle
from paddle_tpu.inference import FleetRouter

def serve_fleet(model, prompts):
    r = FleetRouter(model, replicas=4, standby=1,
                    fleet_slo="queue_p95_ms=200,goodput=0.99",
                    default_deadline_ms=500.0,
                    scalein_hold_s=30.0,
                    migration=True, lameduck_ms=2000.0)
    for p in prompts:
        r.add_request(p, 32)
    return r.run()
""")
def check_cold_drain_under_load(fndef, ctx):
    """A ``FleetRouter`` armed with latency judgment (``fleet_slo`` /
    ``default_deadline_ms`` / ``scalein_hold_s`` — scale-in and drain
    WILL happen, and deadlines tick while they do) but with live
    migration absent or off-spelled.  A cold drain waits out the tail
    decode of every resident request before the replica parks:
    under load that is seconds of deadline burn per scale-in, and a
    planned preemption (SIGTERM) loses every resident request's
    prefill work to a from-scratch requeue.  ``migration=True`` (or
    the ``serving_migration`` flag) moves residents warm instead —
    snapshot -> KV-page transfer -> restore through the import
    scatter; token streams are bitwise-identical
    (tests/test_migration.py gates this), only drain latency and
    re-prefill work move.  Note-level advice: single-replica rigs and
    fleets that never scale in are legitimate."""
    for node in _walk_fn(fndef):
        if not isinstance(node, ast.Call) \
                or (_dotted(node.func) or "").split(".")[-1] \
                != "FleetRouter":
            continue
        kws = {kw.arg for kw in node.keywords if kw.arg}
        if kws & _ROUTER_SLO_KWARGS \
                and _migration_off_or_absent(node):
            yield node, (
                "fleet router is judged on latency (fleet_slo/"
                "default_deadline_ms/scalein_hold_s) but drains cold: "
                "scale-in and preemption wait out every resident "
                "request's tail decode while deadlines tick, and a "
                "SIGTERM loses resident prefill work to a cold "
                "requeue — pass migration=True (or the "
                "serving_migration flag) so residents move warm over "
                "KVPageTransport; token streams are bitwise-"
                "identical, only drain latency moves")
