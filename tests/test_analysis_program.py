"""Whole-program jaxpr analyzer (ISSUE 16): the dataflow framework
(sub-jaxpr walk, def-use/live ranges, static peak-HBM sweep), collective
schedule extraction + the store-backed runtime verifier, eqn-level
provenance of the PDT22x/23x passes, the jit-capture wiring (audit-once,
``hbm.static_peak_bytes`` gauge, PDT242 shape-fork sharing the
``compile.retrace`` vocabulary), and the per-code audit-counts tally."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu import observability as obs
from paddle_tpu.analysis import LintWarning, Severity
from paddle_tpu.analysis import program as prog
from paddle_tpu.core import errors


@pytest.fixture(autouse=True)
def _fresh():
    analysis.reset_reported()
    yield
    analysis.reset_reported()


@pytest.fixture
def _mode():
    old = paddle.get_flags("analysis")["analysis"]

    def set_mode(m):
        paddle.set_flags({"analysis": m})

    yield set_mode
    paddle.set_flags({"analysis": old})


@pytest.fixture
def metrics_on():
    old = paddle.get_flags("metrics")["metrics"]
    paddle.set_flags({"metrics": True})
    yield
    paddle.set_flags({"metrics": old})


# ==========================================================================
# dataflow framework
# ==========================================================================

def test_all_eqns_walks_cond_scan_while_pjit():
    inner = jax.jit(lambda v: v * 3.0)

    def f(p, xs, x):
        y = lax.cond(p, lambda v: v * 2.0, lambda v: v + 1.0, x)
        c, out = lax.scan(lambda c, s: (c + s, c), y, xs)
        (c,) = lax.while_loop(lambda v: v[0].sum() < 10.0,
                              lambda v: (v[0] + 1.0,), (c,))
        return inner(c) + out.sum()

    closed = jax.make_jaxpr(f)(True, jnp.ones((3, 4), jnp.float32),
                               jnp.ones((4,), jnp.float32))
    paths = {p for _, p in prog.all_eqns(closed)}
    assert any(p.startswith("branches[0]") for p in paths), paths
    assert any(p.startswith("branches[1]") for p in paths), paths
    assert any("body_jaxpr" in p for p in paths), paths   # while body
    assert any("cond_jaxpr" in p for p in paths), paths   # while cond
    assert any(p.startswith("jaxpr") for p in paths), paths  # scan/pjit
    # top-level eqns carry the empty path
    assert "" in paths


def test_def_use_and_live_ranges():
    def f(x):
        a = x * 2.0
        b = a + 1.0
        return b

    j = jax.make_jaxpr(f)(jnp.ones((8,), jnp.float32)).jaxpr
    x = j.invars[0]
    uses = prog.def_use(j)
    assert uses[x] == [0]                      # consumed by eqn 0 only
    ranges = prog.live_ranges(j)
    assert ranges[x] == (-1, 0)                # input, dies after eqn 0
    out = j.outvars[0]
    assert ranges[out][1] == len(j.eqns)       # outvar survives program


def test_static_peak_bytes_counts_live_set_and_donation_alias():
    kib = 1024 * 4  # 1024 f32

    def step(w, g):
        return w - 0.1 * g

    closed = jax.make_jaxpr(step)(jnp.ones((1024,), jnp.float32),
                                  jnp.ones((1024,), jnp.float32))
    base = prog.static_peak_bytes(closed)
    assert base >= 3 * kib                     # w, g, out live together
    # donating w (shape/dtype matches the output) aliases it onto the
    # result: the estimate drops by exactly one buffer
    donated = prog.static_peak_bytes(closed, donated=(0,))
    assert donated == base - kib


def test_static_peak_bytes_attributes_inner_scan_peak():
    def f(xs):
        def body(c, s):
            big = jnp.outer(s, s)              # transient inside body
            return c + big.sum(), big.sum()
        return lax.scan(body, 0.0, xs)

    closed = jax.make_jaxpr(f)(jnp.ones((4, 256), jnp.float32))
    peak = prog.static_peak_bytes(closed)
    # the 256x256 transient inside the scan body dominates the
    # top-level live set and must show up in the estimate
    assert peak >= 256 * 256 * 4


# ==========================================================================
# collective schedule + hash
# ==========================================================================

def test_collective_schedule_extraction_and_hash():
    def f(x):
        a = lax.psum(x, "i")
        return lax.pmax(a, "i")

    closed = jax.make_jaxpr(f, axis_env=[("i", 2)])(
        jnp.ones((4,), jnp.float32))
    sched = prog.collective_schedule(closed)
    assert [op.prim for op in sched] == ["psum", "pmax"]
    assert sched[0].axes == ("i",)
    assert sched[0].shape == (4,) and sched[0].dtype == "float32"
    h = prog.schedule_hash(sched)
    assert h == prog.schedule_hash(sched)                  # stable
    assert prog.schedule_hash(list(reversed(sched))) != h  # ordered
    assert prog.schedule_hash([]) != h


def test_collective_schedule_reaches_into_subjaxprs():
    def f(p, x):
        return lax.cond(p, lambda v: lax.psum(v, "i") * 2.0,
                        lambda v: lax.psum(v, "i") + 1.0, x)

    closed = jax.make_jaxpr(f, axis_env=[("i", 2)])(
        True, jnp.ones((4,), jnp.float32))
    sched = prog.collective_schedule(closed)
    assert len(sched) == 2                     # one psum per branch
    assert all(op.path.startswith("branches[") for op in sched)


class _FakeStore:
    """bstore.Store test double: shared dict, StoreTimeoutError on a
    missing key (the real store's timeout contract)."""

    def __init__(self, kv):
        self.kv = kv

    def set(self, k, v):
        self.kv[k] = v

    def get(self, k, timeout=None):
        if k not in self.kv:
            raise errors.StoreTimeoutError(f"no key {k}")
        return self.kv[k]


def test_verify_schedule_agreement_divergence_and_missing_peer():
    kv = {}
    a, b = _FakeStore(kv), _FakeStore(kv)
    # first rank up: the peer has not published yet -> skipped, agrees
    assert prog.verify_schedule(a, "g", "n0", ["n0", "n1"], "aaaa",
                                timeout=0.0)
    # second rank agrees with the published hash
    assert prog.verify_schedule(b, "g", "n1", ["n0", "n1"], "aaaa",
                                timeout=0.0)
    # a divergent rank reports PDT223 and raises the coded error
    with analysis.collect() as diags:
        with pytest.raises(errors.CollectiveScheduleError,
                           match="divergence"):
            prog.verify_schedule(b, "g", "n1", ["n0", "n1"], "bbbb",
                                 timeout=0.0)
    assert any(d.code == "PDT223" for d in diags), \
        [d.format() for d in diags]
    # raise_on_divergence=False: reports, returns False, does not raise
    with analysis.collect() as diags2:
        ok = prog.verify_schedule(b, "g", "n1", ["n0", "n1"], "bbbb",
                                  timeout=0.0, raise_on_divergence=False)
    assert ok is False
    assert any(d.code == "PDT223" for d in diags2)


def test_collective_schedule_error_is_coded():
    assert issubclass(errors.CollectiveScheduleError, errors.EnforceNotMet)
    assert errors.CollectiveScheduleError("x").error_code == "PDT-E023"


# ==========================================================================
# pass provenance (the goldens in test_analysis.py cover trigger /
# near-miss / suppression for every code; here: eqn-level anchoring)
# ==========================================================================

def test_pdt221_divergent_cond_anchors_to_the_cond_eqn():
    def f(p, x):
        return lax.cond(p, lambda v: lax.psum(v, "i"),
                        lambda v: v * 2.0, x)

    closed = jax.make_jaxpr(f, axis_env=[("i", 2)])(
        True, jnp.ones((4,), jnp.float32))
    hits = [d for d in analysis.check_jaxpr(closed)
            if d.code == "PDT221"]
    assert hits and hits[0].severity == Severity.ERROR
    # provenance: the finding carries the cond eqn's user source site —
    # this very file, at a positive line number
    assert hits[0].file.endswith("test_analysis_program.py"), hits[0]
    assert hits[0].line > 0
    assert "branch" in hits[0].message


def test_pdt231_read_after_donation_anchors_to_consuming_eqn():
    def f(w, g):
        return (w - g).sum()                   # no (1024,) output left

    closed = jax.make_jaxpr(f)(jnp.ones((1024,), jnp.float32),
                               jnp.ones((1024,), jnp.float32))
    hits = [d for d in analysis.check_jaxpr(closed, donated=(0,))
            if d.code == "PDT231"]
    assert hits and hits[0].severity == Severity.ERROR
    # provenance: anchored to the eqn that consumed the donated buffer
    assert hits[0].file.endswith("test_analysis_program.py"), hits[0]
    assert hits[0].line > 0
    # near-miss: a matching output supersedes the donated input
    clean = jax.make_jaxpr(lambda w, g: w - g)(
        jnp.ones((1024,), jnp.float32), jnp.ones((1024,), jnp.float32))
    assert not [d for d in analysis.check_jaxpr(clean, donated=(0,))
                if d.code == "PDT231"]


# ==========================================================================
# jit capture wiring: audit-once, gauge, shape-fork retrace vocabulary
# ==========================================================================

def test_capture_audit_stashes_peak_and_schedule_hash():
    w = paddle.to_tensor(np.ones((256,), np.float32))

    @paddle.jit.to_static
    def audited_step(x):
        return (x * 2.0 + w.sum()).mean()

    x = paddle.to_tensor(np.ones((256,), np.float32))
    with analysis.collect():
        audited_step(x)
    exe = audited_step.concrete_program(x)
    assert exe.jaxpr is None                   # still released after audit
    assert exe.static_peak_bytes > 0
    assert exe.schedule_hash == prog.schedule_hash([])  # no collectives

    from paddle_tpu import jit as jit_mod
    assert jit_mod._static_peak_bytes("audited_step") \
        == exe.static_peak_bytes
    assert jit_mod._program_state_bytes("audited_step") > 0


def test_hbm_static_peak_gauge_reads_live_executables(metrics_on):
    from paddle_tpu.observability import metrics as obs_metrics

    w = paddle.to_tensor(np.ones((128,), np.float32))

    @paddle.jit.to_static
    def gauged_step(x):
        return (x + w).sum()

    x = paddle.to_tensor(np.ones((128,), np.float32))
    with analysis.collect():
        gauged_step(x)
    exe = gauged_step.concrete_program(x)
    snap = obs_metrics.registry().snapshot()["hbm"]
    assert snap["static_peak_bytes"]["fn=gauged_step"] \
        == exe.static_peak_bytes
    # sits next to the measured residency gauge, same labels
    assert snap["program_state_bytes"]["fn=gauged_step"] > 0


def test_shape_fork_pdt242_fires_and_shares_retrace_vocabulary(
        metrics_on):
    obs.events.clear()

    @paddle.jit.to_static
    def forked(x):
        return x * 2.0

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        with analysis.collect() as diags:
            for n in (4, 5, 6):                # 3 shape-only variants
                forked(paddle.to_tensor(np.ones((n,), np.float32)))
    hits = [d for d in diags if d.code == "PDT242"]
    assert hits, [d.format() for d in diags]
    assert "shape-as-data" in hits[0].message
    # runtime evidence rides the SAME vocabulary: a compile.retrace
    # event with the shape-as-data cause and the variant count
    retr = [e for e in obs.tail() if e["kind"] == "compile.retrace"
            and e.get("cause", "").startswith("shape-as-data")]
    assert retr and retr[-1]["count"] == 3
    assert retr[-1]["fn"] == "forked"


def test_shape_fork_below_limit_is_silent():
    @paddle.jit.to_static
    def two_shapes(x):
        return x + 1.0

    with analysis.collect() as diags:
        for n in (4, 5):                       # below SHAPE_FORK_LIMIT
            two_shapes(paddle.to_tensor(np.ones((n,), np.float32)))
    assert not [d for d in diags if d.code == "PDT242"]


def test_strip_shapes_collapses_shape_only_variants():
    a = (("T", (4, 8), "float32"), 3, "k")
    b = (("T", (9, 8), "float32"), 3, "k")
    c = (("T", (4, 8), "int32"), 3, "k")
    assert prog.strip_shapes(a) == prog.strip_shapes(b)
    assert prog.strip_shapes(a) != prog.strip_shapes(c)


# ==========================================================================
# audit entry points: counts, mode gating, zero per-dispatch work
# ==========================================================================

def test_audit_counts_accumulate_and_reset():
    analysis.audit_counts(reset=True)
    closed = jax.make_jaxpr(lambda x: x * 2.0)(3.0)  # weak input: PDT205
    with analysis.collect():
        analysis.audit_jaxpr(closed, where="t")
        analysis.audit_jaxpr(closed, where="t")
    assert analysis.audit_counts().get("PDT205", 0) >= 2
    analysis.audit_counts(reset=True)
    assert analysis.audit_counts() == {}


def test_audit_runs_at_capture_not_per_dispatch():
    @paddle.jit.to_static
    def dispatched(x):
        return x + 1.0

    x = paddle.to_tensor(np.ones((4,), np.float32))
    with analysis.collect():
        dispatched(x)                          # capture: audit runs here
    analysis.audit_counts(reset=True)
    dispatched(x)
    dispatched(x)                              # cache hits: zero audit work
    assert analysis.audit_counts() == {}


def test_audit_jitted_and_executable_gated_off(_mode):
    _mode("off")
    assert analysis.audit_jitted(lambda x: x * 2.0,
                                 (jnp.ones((3,), jnp.float32),),
                                 where="t") is None

    class _Exe:
        jaxpr = jax.make_jaxpr(lambda x: x + 1.0)(
            jnp.ones((3,), jnp.float32))

    assert analysis.audit_executable(_Exe(), where="t") is None


def test_audit_jitted_swallows_trace_failures():
    def broken(x):
        raise RuntimeError("tracing explodes")

    assert analysis.audit_jitted(broken, (jnp.ones((3,),),),
                                 where="t") is None
