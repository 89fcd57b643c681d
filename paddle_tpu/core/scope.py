"""Phase scopes: names inside the compiled program.

A ``jit.to_static`` step is one XLA program, and a device trace names
its operations by the ``op_name`` the tracer gave them.  ``phase(name)``
puts the program's own vocabulary there (``gpt/block_3/attn``,
``backward/...``, ``optimizer``, ``loss``) with ``jax.named_scope`` —
and only while a program is being captured (``jit``'s replay sets
``capture()``): eager dispatch gets the shared no-op context and opens
no scope.

The live path is kept per thread beside JAX's own name stack so that a
grad node can remember where it was recorded (``current()``) and
``run_backward`` can re-enter that path under ``backward``.

Whether a program is being captured also decides WHERE the tape
linearises an op (``core/dispatch.py``): as it is recorded under a
capture, at backward time in eager.  ``tape()`` is the tally of both,
one per capture and one per thread for eager work.
"""
from __future__ import annotations

import contextlib
import threading

import jax


class TapeCounts:
    """Grad nodes linearised (``jax.vjp`` of the op's primal) as they
    were recorded, and at backward time."""

    __slots__ = ("record", "backward")

    def __init__(self):
        self.record = self.backward = 0


class _State(threading.local):
    capturing = False
    path = ""       # "/"-joined scopes open on this thread

    def __init__(self):
        self.tape = TapeCounts()    # this thread's eager tally


_state = _State()
_NO_SCOPE = contextlib.nullcontext()


@contextlib.contextmanager
def capture():
    """Set round the replay of a function whose program is being
    captured (``jit._Executable``'s ``pure``).  Yields the capture's
    own ``TapeCounts``."""
    old = _state.capturing, _state.path, _state.tape
    _state.capturing, _state.path, _state.tape = True, "", TapeCounts()
    try:
        yield _state.tape
    finally:
        _state.capturing, _state.path, _state.tape = old


def current():
    """The scope path a grad node recorded now belongs to, or None
    outside a capture."""
    return _state.path if _state.capturing else None


def tape():
    """The live tally: the capture's inside one, else the thread's."""
    return _state.tape


class _Phase:
    __slots__ = ("name", "_outer", "_jax")

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self._outer = _state.path
        _state.path = f"{self._outer}/{self.name}" if self._outer \
            else self.name
        self._jax = jax.named_scope(self.name)
        self._jax.__enter__()
        return self

    def __exit__(self, *exc):
        _state.path = self._outer
        return self._jax.__exit__(*exc)


def phase(name):
    """``with phase("optimizer"): ...``: a named scope in the program
    being captured, nothing in eager.  ``name`` may be a path
    (``gpt/block_3``); an empty one opens nothing."""
    if not _state.capturing or not name:
        return _NO_SCOPE
    return _Phase(name)
