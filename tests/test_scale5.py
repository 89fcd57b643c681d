"""Scale-5 validation (VERDICT r3 item 5): the 3-axis dp x mp x pp
hybrid in one mesh, and the GPT-13B GSPMD train step AOT-lowered on a
32-device virtual mesh with a v5e HBM fit check (reference bar:
``test/auto_parallel/hybrid_strategy/
semi_auto_parallel_simple_net_dp_mp_pp.py`` and the 13B milestone of
BASELINE.md)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle


TP_RULES = {
    "attn.qkv.weight": 2, "attn.qkv.bias": 1,
    "mlp.fc1.weight": 2, "mlp.fc1.bias": 1,
    "attn.proj.weight": 1, "mlp.fc2.weight": 1,
}


def _pipe_run(mesh_shape, names, tp, pl):
    """(the losses of two compiled train steps, the model) of one seeded
    two-layer ``GPTForCausalLMPipe`` on the eight devices laid out as
    ``mesh_shape``: the data, the seed and the optimizer are the same
    whatever the layout."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe

    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=32, dropout=0.0)
    rng0 = np.random.default_rng(7)
    # batch 8: divisible by microbatches(2) x dp for both meshes
    data = [(rng0.integers(0, 128, (8, 16)).astype(np.int32),
             rng0.integers(0, 128, (8, 16)).astype(np.int32))
            for _ in range(2)]
    mesh = dist.ProcessMesh(np.arange(8).reshape(*mesh_shape), names)
    paddle.seed(0)
    model = GPTForCausalLMPipe(cfg, mesh, pp_axis="pp", dp_axis="dp",
                               num_microbatches=2)
    if tp:
        model.blocks.shard(mesh, "pp", tp_axis="mp", tp_rules=TP_RULES)
    model.train()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())

    @paddle.jit.to_static
    def step(ids, labels):
        loss = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return [float(step(dist.shard_tensor(ids, mesh, pl),
                       dist.shard_tensor(labels, mesh, pl)))
            for ids, labels in data], model


@pytest.fixture(scope="module")
def dp_mp_pp():
    """The 3-axis hybrid's run, built and stepped once for the two cases
    that read it.  The same layout at ``__graft_entry__``'s 128-wide toy
    under AdamW is tests/test_models.py's
    ``test_dryrun_multichip_dp_mp_pp``."""
    import paddle_tpu.distributed as dist
    return _pipe_run((2, 2, 2), ["dp", "mp", "pp"], True,
                     [dist.Shard(0), dist.Replicate(), dist.Replicate()])


def test_dp_mp_pp_single_mesh(dp_mp_pp):
    """GPipe over pp + Megatron TP over mp (GSPMD inside the pipeline
    shard_map via auto axes) + dp batch sharding, one mesh, full train
    step."""
    losses, model = dp_mp_pp
    assert all(np.isfinite(l) for l in losses)
    # stacked qkv must carry BOTH pp (dim 0) and mp (dim 2) sharding
    w = model.blocks.stacked_parameter("attn.qkv.weight")._read()
    spec = str(getattr(w.sharding, "spec", ""))
    assert "pp" in spec and "mp" in spec, spec


def test_dp_mp_pp_matches_dp_only(dp_mp_pp):
    """The 3-axis hybrid must compute the same losses as plain dp on the
    same seed/data (parallelism is an implementation detail)."""
    import paddle_tpu.distributed as dist
    got, _ = dp_mp_pp
    ref, _ = _pipe_run((4, 2), ["dp", "pp"], False,
                       [dist.Shard(0), dist.Replicate()])
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_gpt13b_aot_lowering_fits_v5e():
    """Lower + compile the 13B train step on a 32-device virtual mesh in
    a fresh process (needs 32 devices; the suite mesh has 8) and assert
    the per-device resident memory fits v5e HBM."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "aot",
                                      "aot_gpt13b.py")],
        env=env, cwd=root, capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "AOT 13B OK" in r.stdout
    assert "tiny equivalence" in r.stdout


@pytest.mark.slow
def test_gpt13b_capture_path_aot_lowering():
    """VERDICT r4 item 9: the framework's OWN capture path — LazyGuard
    GPTForCausalLM + shard_gpt + AMP O2 + ZeRO-1 + jit.aot_lower — must
    lower and compile at the 13B config on 32 virtual devices with the
    same HBM fit (fresh process: needs 32 devices).

    ``slow``: a 13B lowering in a fresh 32-device CPU subprocess is the
    single most expensive test in the repo (~6 min alone — nearly half
    the tier-1 870s budget, which was clipping the trailing vision
    files; PR7 budget audit); run it with ``-m slow``."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "aot",
                                      "aot_capture_13b.py")],
        env=env, cwd=root, capture_output=True, text=True, timeout=2400)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "AOT CAPTURE 13B OK" in r.stdout
