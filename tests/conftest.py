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


@pytest.fixture(scope="session")
def serving_gpt():
    """ONE tiny GPT for the serving test modules.  Compiled generate and
    engine programs cache on the model instance, and a session is one
    ``xdist`` worker: under ``--dist loadfile`` the cases of ONE file
    that drive the same geometries and prompt lengths reuse each other's
    programs, and two files do only where a worker happens to get both.
    Budget, not semantics: the model is in eval mode and seeded, so
    sharing changes no numbers."""
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
    heads, rotary, RMS norm, SwiGLU), one for the session (a worker) as
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
