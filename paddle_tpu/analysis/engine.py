"""Diagnostics engine: runs the registered checks, applies suppression,
and reports findings according to ``FLAGS_analysis``
(``PDTPU_ANALYSIS=off|warn|error``).

Entry points:

- :func:`analyze_source` / :func:`analyze_file` — AST front-end over
  source text (the CLI and the pre-conversion lint).
- :func:`check_function` — AST front-end over a live callable.
- :func:`check_jaxpr` / :func:`check_traced` / :func:`check_executable`
  — IR front-end over a traced program.
- :func:`report` / :func:`report_runtime` — route findings per the mode
  flag: ``off`` drops them, ``warn`` emits :class:`LintWarning`
  (notes go to the module logger), ``error`` raises
  :class:`~paddle_tpu.core.errors.StaticAnalysisError` on any finding of
  warn severity or above.
- :func:`collect` — context manager capturing findings into a list
  instead of reporting (tests, tooling).
"""
from __future__ import annotations

import ast
import inspect
import logging
import textwrap
import warnings
from dataclasses import dataclass, field
from typing import Iterable, Optional

from ..core import state
from .registry import (REGISTRY, CheckSpec, Diagnostic, Severity,
                       active_suppressions, decorator_name,
                       pragma_suppressed)

logger = logging.getLogger("paddle_tpu.analysis")

_MODES = ("off", "warn", "error")


class LintWarning(UserWarning):
    """Category for analyzer findings reported in ``warn`` mode."""


def mode() -> str:
    try:
        m = str(state.get_flag("analysis")).lower()
    except KeyError:
        return "warn"
    return m if m in _MODES else "warn"


# --------------------------------------------------------------------------
# collection sink (tests/tooling) + session-level dedup
# --------------------------------------------------------------------------

# Process-global like the suppression stack (registry._SuppressState):
# runtime reports may arrive from a jax callback thread.
class _Sinks:
    def __init__(self):
        self.stack: list[list] = []


_sinks = _Sinks()
_reported: set[tuple] = set()


class collect:
    """``with analysis.collect() as diags:`` captures every finding that
    would have been reported (regardless of mode) into ``diags`` —
    process-wide, so callback-thread runtime reports land too."""

    def __enter__(self):
        self._sink: list[Diagnostic] = []
        _sinks.stack.append(self._sink)
        return self._sink

    def __exit__(self, *exc):
        stack = _sinks.stack
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is self._sink:
                del stack[i]
                break
        return False


def reset_reported():
    """Clear the session dedup set (one report per (code, site))."""
    _reported.clear()


# --------------------------------------------------------------------------
# AST front-end
# --------------------------------------------------------------------------

@dataclass
class _AstCtx:
    filename: str
    lines: list[str]
    line_offset: int = 0
    decorated: bool = False


def _is_to_static_decorator(dec) -> bool:
    return decorator_name(dec) == "to_static"


def _iter_functions(tree, force_jit):
    """(fndef, decorated, in_jit) for EVERY function: in_jit when
    decorated with ``to_static``, forced, or NESTED inside a jit
    function (inline helpers are traced too). Each nested def is
    yielded as its own scope — the AST checks do not descend into
    nested defs — so per-function suppression binds to the right
    function. Checks with scope "jit" run on in-jit functions, scope
    "eager" on the rest."""
    def visit(node, in_jit):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                decorated = any(_is_to_static_decorator(d)
                                for d in child.decorator_list)
                jit = decorated or force_jit or in_jit
                yield child, decorated, jit
                yield from visit(child, jit)
            else:
                yield from visit(child, in_jit)

    yield from visit(tree, False)


def _decorator_suppressions(fndef):
    """Codes silenced by ``@analysis.suppress("PDT1xx", ...)`` decorators,
    read syntactically so source-only analysis (the CLI) matches the
    runtime tag the decorator sets. ``None`` means suppress everything
    (a bare ``@suppress()``)."""
    out: set[str] = set()
    for dec in fndef.decorator_list:
        if decorator_name(dec) != "suppress" or not isinstance(dec, ast.Call):
            continue
        if not dec.args:
            return None
        for a in dec.args:
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                out.add(a.value.upper())
    return out


def _span_suppressed(lines: list[str], lo: int, hi: int, code: str) -> bool:
    """True when any line of the 1-based inclusive span ``lo..hi``
    carries a noqa pragma covering ``code``. Suppression anchors to the
    STATEMENT's full line span, not a single line — a pragma anywhere on
    a decorated def (decorator lines included) or a multiline statement
    suppresses findings anchored anywhere in it."""
    lo = max(1, lo)
    hi = min(len(lines), hi)
    return any(pragma_suppressed(lines[i - 1], code)
               for i in range(lo, hi + 1))


def _def_span(fndef) -> tuple[int, int]:
    """Line span of a function's HEADER: first decorator line through
    the end of the signature (the line before the first body
    statement). A pragma anywhere in it opts the whole function out."""
    lo = min([d.lineno for d in fndef.decorator_list] + [fndef.lineno])
    hi = fndef.body[0].lineno - 1 if fndef.body else fndef.lineno
    return lo, max(lo, hi)


def analyze_source(source: str, filename: str = "<string>", *,
                   force_jit: bool = False, line_offset: int = 0,
                   extra_suppress: frozenset = frozenset()
                   ) -> list[Diagnostic]:
    """Run every AST check over ``source``; returns surviving findings.

    Only functions in a jit context are checked: decorated with
    ``to_static`` (any dotted spelling), or all of them under
    ``force_jit``. Suppression (pragma, active ``suppress`` contexts,
    ``extra_suppress``) is applied here."""
    try:
        tree = ast.parse(textwrap.dedent(source))
    except SyntaxError:
        return []
    lines = textwrap.dedent(source).splitlines()
    suppressed = active_suppressions() | extra_suppress
    out: list[Diagnostic] = []
    seen: set[tuple] = set()
    for fndef, decorated, in_jit in _iter_functions(tree, force_jit):
        ctx = _AstCtx(filename=filename, lines=lines,
                      line_offset=line_offset, decorated=decorated)
        def_lo, def_hi = _def_span(fndef)
        dec_sup = _decorator_suppressions(fndef)
        if dec_sup is None:
            continue  # bare @suppress(): whole function opted out
        for spec in REGISTRY.values():
            if spec.frontend != "ast" or spec.func is None:
                continue
            if spec.scope != "any" and (spec.scope == "jit") != in_jit:
                continue
            if spec.code in suppressed or spec.code in dec_sup:
                continue
            for node, message in spec.func(fndef, ctx):
                rel = getattr(node, "lineno", fndef.lineno)
                col = getattr(node, "col_offset", 0)
                key = (spec.code, rel, col, message)
                if key in seen:
                    continue
                seen.add(key)
                end = getattr(node, "end_lineno", None) or rel
                if _span_suppressed(lines, rel, max(rel, end),
                                    spec.code) or \
                        _span_suppressed(lines, def_lo, def_hi,
                                         spec.code):
                    continue
                out.append(Diagnostic(
                    code=spec.code, severity=spec.severity,
                    message=message, file=filename,
                    line=rel + line_offset, col=col))
    out.sort(key=lambda d: (d.line, d.col, d.code))
    return out


def analyze_file(path: str, *, force_jit: bool = False) -> list[Diagnostic]:
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        src = f.read()
    return analyze_source(src, filename=str(path), force_jit=force_jit)


def _unwrap_callable(fn):
    for attr in ("fn", "__func__", "__wrapped_original__"):
        inner = getattr(fn, attr, None)
        if inner is not None and callable(inner):
            fn = inner
    return fn


def check_function(fn, *, jit: bool = True) -> list[Diagnostic]:
    """AST-lint a live callable (methods/StaticFunctions unwrapped).
    Returns [] when source is unavailable."""
    fn = _unwrap_callable(fn)
    extra = frozenset(getattr(fn, "__pdtpu_suppress__", frozenset()))
    try:
        src_lines, start = inspect.getsourcelines(fn)
        filename = inspect.getsourcefile(fn) or "<unknown>"
    except (OSError, TypeError):
        return []
    return analyze_source("".join(src_lines), filename=filename,
                          force_jit=jit, line_offset=start - 1,
                          extra_suppress=extra)


# --------------------------------------------------------------------------
# IR front-end
# --------------------------------------------------------------------------

@dataclass
class _IrCtx:
    donated: frozenset = frozenset()
    n_explicit_args: int = 0
    where: str = "<jaxpr>"


def _eqn_site(eqn):
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            return frame.file_name, frame.start_line
    except Exception:
        pass
    return None


def check_jaxpr(closed, *, donated: Iterable[int] = (),
                n_explicit_args: int = 0, where: str = "<jaxpr>",
                extra_suppress: frozenset = frozenset()
                ) -> list[Diagnostic]:
    """Run every IR check over a ClosedJaxpr; returns surviving
    findings. ``donated`` are invar indices the program donates;
    ``n_explicit_args`` marks the leading caller-owned inputs."""
    suppressed = active_suppressions() | frozenset(extra_suppress)
    ctx = _IrCtx(donated=frozenset(donated),
                 n_explicit_args=int(n_explicit_args), where=where)
    out: list[Diagnostic] = []
    for spec in REGISTRY.values():
        if spec.frontend != "ir" or spec.func is None:
            continue
        if spec.code in suppressed:
            continue
        try:
            findings = list(spec.func(closed, ctx))
        except Exception:  # a broken check must never break the build
            logger.debug("IR check %s failed", spec.code, exc_info=True)
            continue
        for message, eqn in findings:
            site = _eqn_site(eqn) if eqn is not None else None
            file, line = site if site else (where, 0)
            out.append(Diagnostic(code=spec.code, severity=spec.severity,
                                  message=message, file=file, line=line))
    return out


def check_traced(fn, *args, **kwargs) -> list[Diagnostic]:
    """Trace ``fn`` with jax.make_jaxpr and IR-lint the result."""
    import jax
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return check_jaxpr(closed, where=getattr(fn, "__name__", "<fn>"))


def check_executable(exe, where: str = "<to_static>",
                     extra_suppress: frozenset = frozenset()
                     ) -> list[Diagnostic]:
    """IR-lint a built ``jit._Executable`` (uses the jaxpr and donation
    info captured at build time; [] once the jaxpr has been released
    after the post-capture lint)."""
    closed = getattr(exe, "jaxpr", None)
    if closed is None:
        return []
    return check_jaxpr(
        closed, donated=getattr(exe, "donate_idx", ()),
        n_explicit_args=getattr(exe, "n_explicit_args", 0), where=where,
        extra_suppress=extra_suppress)


# --------------------------------------------------------------------------
# reporting
# --------------------------------------------------------------------------

def report(diags: list[Diagnostic], *, where: str = "", dedup: bool = True,
           allow_raise: bool = True) -> None:
    """Route findings per the mode flag. With ``dedup`` (default), a
    site — (code, file, line); message ignored because the AST linter
    and dy2static's decline path can describe the same graph break
    differently — reports once per session, EXCEPT in error mode, where
    a blocking site keeps raising until it is fixed or suppressed
    (nothing is marked reported when we raise)."""
    if not diags:
        return
    if _sinks.stack:
        _sinks.stack[-1].extend(diags)
        return
    m = mode()
    if m == "off":
        return
    prefix = f"[{where}] " if where else ""
    if m == "error" and allow_raise:
        # the gate ignores the dedup set: a blocking site keeps raising
        # even if it was already surfaced as a warning in warn mode
        blocking = [d for d in diags if d.severity >= Severity.WARN]
        if blocking:
            from ..core.errors import StaticAnalysisError
            raise StaticAnalysisError(
                prefix + "static analysis found "
                f"{len(blocking)} blocking finding(s) "
                f"(PDTPU_ANALYSIS=error):\n"
                + "\n".join("  " + d.format() for d in blocking))
    fresh = [d for d in diags
             if not dedup or (d.code, d.file, d.line) not in _reported]
    if not fresh:
        return
    if dedup:
        for d in fresh:
            _reported.add((d.code, d.file, d.line))
    for d in fresh:
        if d.severity == Severity.NOTE:
            logger.info("%s%s", prefix, d.format())
        else:
            warnings.warn(prefix + d.format(), LintWarning, stacklevel=3)


def report_runtime(code: str, message: str, *, file: str = "<runtime>",
                   line: int = 0) -> None:
    """Report a runtime-produced diagnostic (e.g. PDT206 from inside a
    compiled program) through the mode/suppression funnel. Runtime
    findings are never deduped (each occurrence is a distinct event —
    two different loops truncating must both surface) and never raise
    even in error mode: they fire mid-execution, often from inside a
    ``jax.debug.callback``, where an exception would abort the step with
    a corrupted result instead of gating it."""
    spec: Optional[CheckSpec] = REGISTRY.get(code)
    if spec is None or code in active_suppressions():
        return
    diag = Diagnostic(code=code, severity=spec.severity, message=message,
                      file=file, line=line)
    if _sinks.stack or mode() != "off":
        report([diag], dedup=False, allow_raise=False)
    elif spec.severity >= Severity.WARN:
        # even with the lint off, a warn-severity runtime event (e.g. a
        # truncated while_loop = wrong numerics) must not go silent
        warnings.warn(diag.format(), LintWarning, stacklevel=2)


# --------------------------------------------------------------------------
# wiring entry points (called from jit.to_static / hapi.Model.prepare)
# --------------------------------------------------------------------------

def lint_callable(fn, *, where: str = "") -> list[Diagnostic]:
    """AST-lint ``fn`` and report. The to_static/hapi hook: a no-op when
    the flag is off; never raises except StaticAnalysisError in error
    mode."""
    if mode() == "off":
        return []
    try:
        diags = check_function(fn, jit=True)
    except Exception:
        logger.debug("lint_callable failed", exc_info=True)
        return []
    report(diags, where=where or getattr(fn, "__name__", ""))
    return diags


def lint_executable(exe, *, where: str = "", fn=None) -> list[Diagnostic]:
    """IR-lint a built executable and report (the post-capture hook).
    ``fn`` is the source function the capture came from — its
    ``@analysis.suppress`` tag covers IR findings too."""
    if mode() == "off":
        return []
    extra = frozenset()
    if fn is not None:
        extra = frozenset(getattr(_unwrap_callable(fn),
                                  "__pdtpu_suppress__", frozenset()))
    try:
        diags = check_executable(exe, where=where or "<to_static>",
                                 extra_suppress=extra)
    except Exception:
        logger.debug("lint_executable failed", exc_info=True)
        return []
    report(diags, where=where)
    return diags


# --------------------------------------------------------------------------
# registry self-exercise (the golden test and the CLI --explain both use
# this): run a spec's example / near_miss through its front-end.
# --------------------------------------------------------------------------

def exercise(spec: CheckSpec, which: str = "example") -> list[Diagnostic]:
    """Execute a registry snippet and return the diagnostics it yields.

    ``ast`` snippets are analyzed as source (every function treated per
    its decorators); ``ir`` snippets are executed and must define
    ``JAXPR`` (plus optional ``DONATED``/``N_ARGS``); ``runtime``
    snippets are executed and must define ``DIAGS`` (usually via
    ``analysis.collect``)."""
    src = textwrap.dedent(getattr(spec, which))
    if spec.frontend == "ast":
        return analyze_source(src, filename=f"<{spec.code}:{which}>")
    ns: dict = {}
    exec(compile(src, f"<{spec.code}:{which}>", "exec"), ns)  # noqa: S102
    if spec.frontend == "ir":
        return check_jaxpr(ns["JAXPR"],
                           donated=ns.get("DONATED", frozenset()),
                           n_explicit_args=ns.get("N_ARGS", 0),
                           where=f"<{spec.code}:{which}>")
    return list(ns["DIAGS"])
