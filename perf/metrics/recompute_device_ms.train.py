"""Device self time per step of the forward operations run again
inside the backward pass: under ``backward`` and either
``jax.checkpoint``'s ``rematted_computation`` or a ``jvp(...)`` scope
with no ``transpose(...)`` (the forward that ``run_backward``'s
linearisation at backward time traces)."""
from perf import phase_reduce


def read(run):
    return phase_reduce.device_ms(run, "recompute")
