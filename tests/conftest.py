"""Test fixture: force an 8-device virtual CPU mesh (the "fake backend"
pattern of the reference's fake_cpu_device.h plugin tests, SURVEY §4) so
single-host CI can exercise all sharding paths without TPU hardware.

``JAX_PLATFORMS`` and ``XLA_FLAGS`` are read when JAX starts its first
backend, so both are set here, before anything imports JAX.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import pytest

# ---------------------------------------------------------------------------
# Tier-1 budget ordering (ISSUE 7 satellite).  The tier-1 gate runs the
# suite under a hard 870s timeout, so whatever collects LAST is what a
# slow machine silently drops.  Alphabetical order put the expensive
# serving/generation block and the vision model zoo right where the
# cutoff lands, clipping dozens of sub-second tests queued behind them.
# Order files by measured passing-tests-per-second instead (PR7 timing
# audit, full-suite --durations=0 run), with the acceptance-critical
# kernel/serving suites pinned in-window and the known-failing
# distributed/pipeline/scale5 classes (0 dots either way) at the very
# end: a timeout now costs the fewest, least-informative tests.  Files
# not listed (future suites) run right after the pinned block — inside
# the budget by default.  Regenerate the order from a --durations=0 run
# when the balance shifts.
# ---------------------------------------------------------------------------
_TIER1_ORDER = [
    # dense: hundreds of fast tests, ~270s total.  test_tracing is the
    # ISSUE-12 acceptance suite (trace export golden, fleet_snapshot
    # merge, rpc propagation) — model-free except the export acceptance
    # drill, which reuses the session serving_gpt
    # test_slo_watchdog is the ISSUE-14 acceptance suite (burn-rate
    # math, engine_stall drill, regress CLI) — model-free except the
    # engine drills, which reuse the session serving_gpt + the
    # serving-suite geometry
    "test_prefix_cache.py", "test_observability.py", "test_tracing.py",
    "test_slo_watchdog.py",
    # ISSUE-11 acceptance: fused-backward bitwise parity + overlap
    # grad-sync bitwise gates — model-free/tiny-model, ~80s combined
    "test_flash_bwd.py", "test_overlap.py",
    # ISSUE-19 acceptance: remat bitwise family, fused glue twin
    # parity, static-peak drop, prefetch overlap — tiny models, CPU
    "test_train_perf.py",
    "test_profiler_device.py",
    # ISSUE-16 acceptance: whole-program jaxpr analyzer (collective
    # schedule hash/verify, donation provenance, shape-fork PDT242) —
    # model-free tiny jaxprs, a few seconds total
    "test_native_io.py", "test_analysis.py", "test_analysis_program.py",
    "test_autograd.py",
    "test_tensor.py", "test_geometric_namespaces.py",
    "test_optimizer.py", "test_optimizer_fused.py",
    "test_control_flow.py", "test_resilience.py",
    # ISSUE-15 acceptance: elastic recovery drills (buddy restore loss
    # parity, PDT-E021 flight dump, store-key GC) — tiny-model thread
    # fleets over loopback TCPStores, ~2 min wall dominated by the
    # deliberate heartbeat/collective deadlines
    "test_elastic_train.py",
    "test_dist_checkpoint.py", "test_dy2static.py",
    "test_text_audio.py", "test_datasets_transforms_breadth.py",
    "test_autotune.py", "test_nn.py",
    "test_distribution_multivariate.py", "test_errors_static.py",
    "test_beam_decode.py", "test_ops_special.py", "test_incubate.py",
    "test_ps.py", "test_io_workers.py", "test_jit_save_load.py",
    "test_sparse_lbfgs.py", "test_advice_fixes.py",
    "test_ops_extra.py", "test_auto_tuner.py", "test_jit.py",
    "test_quantization.py", "test_auto_parallel.py",
    "test_sparse_breadth.py", "test_vision_ops_inference.py",
    "test_rnn.py",
    # pinned acceptance block: kernels + serving parity (fp, quant,
    # speculative — test_speculative reuses the session model and the
    # serving-engine geometries, so it rides the same compiled
    # programs; test_distserve is the ISSUE-13 TP/disagg acceptance
    # suite and reuses the session serving_gpt + the same geometry)
    "test_pallas.py", "test_quant_serving.py", "test_serving_engine.py",
    "test_speculative.py", "test_distserve.py",
    # test_router is the ISSUE-17 fleet-routing acceptance suite; it
    # reuses the session serving_gpt + the same geometry, so every
    # replica engine rides the already-compiled serving programs
    "test_router.py",
    # test_migration is the ISSUE-20 acceptance suite (live request
    # migration & graceful drain); it reuses the session serving_gpt +
    # the serving-suite geometry, so every engine on both sides of a
    # move rides the already-compiled serving programs
    "test_migration.py",
    # <- unlisted files slot in here (rank _TIER1_DEFAULT)
    # medium density; the budget cutoff lands somewhere below
    "test_fft_signal_distribution.py", "test_op_tail.py",
    "test_rpc_store.py", "test_fleet.py", "test_generation.py",
    "test_ops_table.py", "test_llama.py", "test_analysis_selflint.py",
    "test_launch.py", "test_hapi_vision.py", "test_models.py",
    "test_lenet_e2e.py", "test_elastic.py", "test_moe.py",
    "test_bert.py", "test_vision_models_breadth.py",
    # the distributed/pipeline/ring classes stay tail-ordered (slow
    # compiles, few tests each)
    "test_multihost.py", "test_distributed.py", "test_pipeline.py",
    "test_ring_attention.py", "test_pipeline_schedules.py",
    "test_scale5.py",
]
_TIER1_RANK = {name: i for i, name in enumerate(_TIER1_ORDER)}
_TIER1_DEFAULT = _TIER1_ORDER.index("test_fft_signal_distribution.py") \
    - 0.5  # unlisted files: right after the pinned acceptance block


def pytest_collection_modifyitems(config, items):
    items.sort(key=lambda it: _TIER1_RANK.get(
        it.fspath.basename, _TIER1_DEFAULT))  # stable: in-file order kept


@pytest.fixture(scope="session")
def serving_gpt():
    """ONE tiny GPT shared by the serving test modules
    (test_serving_engine, test_quant_serving): compiled generate/engine
    programs cache on the model instance, so suites that drive the same
    geometries and prompt lengths reuse each other's programs instead
    of recompiling — tier-1 budget, not semantics (the model is eval
    mode and seeded; sharing changes no numbers)."""
    import numpy as np  # noqa: F401  (keep heavy imports lazy)
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=64, dropout=0.0))
    m.eval()
    return m


@pytest.fixture(scope="session")
def serving_llama_gqa():
    """The LLaMA of the serving suites (4 query heads on 2 key/value
    heads, rotary, RMS norm, SwiGLU), one for the session as
    ``serving_gpt`` is: the same vocabulary, so the same prompts."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=64))
    m.eval()
    return m


@pytest.fixture(params=["gpt", "llama_gqa"])
def serving_lm(request, serving_gpt, serving_llama_gqa):
    """Both served families, for the cases that must hold with rotary
    keys of two kv heads under four query heads as well as GPT's."""
    return {"gpt": serving_gpt, "llama_gqa": serving_llama_gqa}[
        request.param]
