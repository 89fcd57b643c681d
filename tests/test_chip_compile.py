"""The main path's Pallas kernels, and the serving engine's own programs
round them, compiled for a DESCRIBED TPU v5e with ``interpret=False`` —
what the chip's compiler refuses fails here, on the CPU, before it
costs chip time.  Nothing runs there: shapes go in, a compiled program
(or the compiler's refusal) comes out.

Rules of this file (on-chip-measurement guide, section 2): the topology
is described inside a module-scoped fixture that skips when it cannot
be — never at import, in a ``skipif`` or in ``parametrize``; the
fixture is not ``autouse`` and not in ``conftest.py``; no child process;
and these tests stay in ONE file, because only one process may load the
TPU's library.  Shapes are GPT-124M's (12 heads, head_dim 64) and the
8B-class GQA geometry (32/8 heads, head_dim 128).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas as P
from paddle_tpu.ops.pallas import flash_attention as FA
from paddle_tpu.ops.pallas import fused_optimizer as FO
from paddle_tpu.ops.pallas import fused_residual_norm as FRN
from paddle_tpu.ops.pallas import paged_attention as PA

BF16, F32 = jnp.bfloat16, jnp.float32
PAGE, TABLE = 16, 80            # page size, pages per block table row


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)``: an argument living on the described chip;
    ``chip.compile(fn, *args)``: ``fn`` compiled for it."""
    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    arg.compile = lambda fn, *args: jax.jit(fn).lower(*args).compile()
    return arg


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


def _paged(chip, kind, hq, hk, d, dtype, pages=512, pool_dtype=None,
           scales=False):
    slots, tokens = 4, 64
    pool = chip((hk, pages, PAGE, d), pool_dtype or dtype)
    args = [chip((slots if kind == "decode" else tokens, hq, d), dtype),
            pool, pool, chip((slots, TABLE), jnp.int32),
            chip((slots,), jnp.int32)]
    if kind == "ragged":
        args.append(chip((slots,), jnp.int32))
    if scales:
        args += [chip((hk, pages, PAGE), F32)] * 2

    def call(q, k, v, bt, kv_lens, *rest):
        kw = dict(interpret=False)
        if scales:
            kw.update(k_scales=rest[-2], v_scales=rest[-1])
        if kind == "ragged":
            return PA.ragged_paged_attention(q, k, v, bt, kv_lens,
                                             rest[0], **kw)
        return PA.paged_decode_attention(q, k, v, bt, kv_lens, **kw)

    return chip.compile(call, *args)


# ------------------------------------------------ the default serving path
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("hq,hk,d", [(12, 12, 64), (32, 8, 128)],
                         ids=["12h_d64", "32_8h_d128"])
@pytest.mark.parametrize("kind", ["ragged", "decode"])
def test_paged_attention_compiles(chip, kind, hq, hk, d, dtype):
    assert _has_kernel(_paged(chip, kind, hq, hk, d, dtype))


def test_paged_pools_stay_in_hbm_d128(chip):
    """Page windows are fetched from pools that stay where they are:
    four times the pages, the same temporaries."""
    small = _paged(chip, "ragged", 32, 8, 128, F32, pages=512)
    large = _paged(chip, "ragged", 32, 8, 128, F32, pages=2048)
    a, b = small.memory_analysis(), large.memory_analysis()
    assert b.argument_size_in_bytes > 3 * a.argument_size_in_bytes
    assert b.temp_size_in_bytes == a.temp_size_in_bytes


@pytest.mark.xfail(strict=True, reason=(
    "XLA's TPU layout for a [Hk, P, 16, 64] pool is {1,3,2,0:T(8,128)} "
    "— pages minor, so that 64 lanes are not padded to 128 — and a "
    "Mosaic operand is row-major: the compiled program copies both "
    "pools into the kernel's layout on every call (temp = 2x the padded "
    "pool).  Needs a pool whose minor dim is a multiple of 128 "
    "(ROADMAP S2)."))
def test_paged_pools_stay_in_hbm_d64(chip):
    small = _paged(chip, "ragged", 12, 12, 64, F32, pages=2048)
    large = _paged(chip, "ragged", 12, 12, 64, F32, pages=8192)
    a, b = small.memory_analysis(), large.memory_analysis()
    assert b.temp_size_in_bytes == a.temp_size_in_bytes


# ---------------------------------------------- the engine's own programs
@pytest.fixture(scope="module", params=["gpt_12h_d64",
                                        "llama_32_8h_d128"])
def served(request):
    """One layer at the family's head geometry, served here on the CPU
    for one request, so that the engine holds its decode step and its
    mixed step as captured programs."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    paddle.seed(0)
    if request.param.startswith("gpt"):
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        model = GPTForCausalLM(GPTConfig(
            vocab_size=512, hidden_size=768, num_layers=1, num_heads=12,
            max_seq_len=64, dropout=0.0))
    else:
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=512, hidden_size=4096, num_layers=1, num_heads=32,
            num_kv_heads=8, max_seq_len=64, intermediate_size=1024))
    model.eval()
    eng = ContinuousBatchingEngine(
        model, max_slots=8, page_size=PAGE, max_seq_len=64,
        decode_window=4, prefill_chunk=16, q_block=8)
    eng.add_request(np.arange(5, dtype=np.int32), 8)
    eng.run()
    assert eng.stats["decode_dispatches"] >= 2      # step, then a window
    return eng


def _on_chip(chip, tree):
    return jax.tree.map(lambda a: chip(a.shape, a.dtype), tree)


@pytest.mark.parametrize("program", ["window", "mixed"])
def test_engine_program_compiles(chip, served, program, monkeypatch):
    """The programs the engine dispatches, whole, for the described
    chip: the scanned decode window over the captured decode step, and
    the mixed prefill+decode step.  Traced again with the kernels not
    interpreted, from the shapes the engine ran here."""
    from paddle_tpu.inference.engine import _make_slot_window
    monkeypatch.setattr(P, "use_interpret", lambda: False)
    if program == "window":
        exe = served._decode_exe
        caches = [c._read() for c in served._caches]
        carry_idx, const_idx = exe.state_split()
        state = [t._read() for t in exe.capt_state]
        b = served.max_slots
        vec = lambda dt: chip((b,), dt)                    # noqa: E731
        lowered = _make_slot_window(exe, served.decode_window).lower(
            chip((b, 1), jnp.int32), vec(jnp.int32), vec(jnp.bool_),
            vec(jnp.bool_), vec(jnp.int32), vec(jnp.int32), vec(F32),
            chip(served._bt.shape, jnp.int32), _on_chip(chip, caches),
            _on_chip(chip, [state[i] for i in carry_idx]),
            _on_chip(chip, [state[i] for i in const_idx]))
    else:
        (exe,) = served._get_mixed_fn()._cache.values()
        # a new function, so that no trace made here on the CPU is reused
        lowered = jax.jit(lambda *vals: exe._pure(*vals)).lower(
            *[chip(shape, jnp.dtype(dt)) for shape, dt in exe._sig0])
    assert _has_kernel(lowered.compile())


@pytest.mark.parametrize("program", ["window", "mixed"])
def test_engine_tp_program_compiles(topo, served, program, monkeypatch):
    """The same two programs of a ``mesh=`` engine, for all four
    described chips: one manual ``shard_map`` each, heads cut four
    ways, the kernels inside it on each chip's own heads."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import generation as G
    monkeypatch.setattr(P, "use_interpret", lambda: False)
    eng = ContinuousBatchingEngine(
        served.model, mesh=Mesh(np.asarray(jax.devices()[:4]), ("tp",)),
        max_slots=served.max_slots, page_size=PAGE, max_seq_len=64,
        decode_window=served.decode_window, prefill_chunk=16, q_block=8)
    tpp, mesh = eng._tpp, Mesh(np.asarray(topo.devices), ("tp",))

    def on(spec, shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    def rep(shape, dtype=jnp.int32):
        return on(PartitionSpec(), shape, dtype)

    cspec = G.tp_cache_spec(tpp.meta, "tp")
    sharded = [on(spec, v.shape, v.dtype)
               for spec, v in zip(tpp.specs, tpp.vals)] \
        + [on(cspec, c.shape, c._read().dtype) for c in eng._caches]
    b, t, n = eng.max_slots, eng.token_budget, len(eng._caches)
    bt = rep(eng._bt.shape)
    if program == "window":
        lowered = G.make_tp_window(
            eng.model, tpp, mesh, eng.pages_per_block, n,
            eng.decode_window).lower(
                rep((b, 1)), rep((b,)), rep((b,), jnp.bool_),
                rep((b,), jnp.bool_), rep((b,)), rep((b,)),
                rep((b,), F32), bt, *sharded)
    else:
        lowered = G.make_tp_mixed(
            eng.model, tpp, mesh, eng.q_block, eng.pages_per_block,
            n).lower(
                rep((1, t)), rep((t,)), rep((t,)), rep((t,)), rep((b,)),
                rep((b,)), rep((b,)), rep((b,), F32), bt, *sharded)
    assert _has_kernel(lowered.compile())


# ------------------------------------------------------ the trainer's path
@pytest.mark.parametrize("shape,causal", [
    pytest.param((8, 1024, 12, 64), True, id="gpt-124m"),
    pytest.param((8, 1024, 16, 64), True, id="gpt2-medium"),
    pytest.param((16, 512, 16, 64), False, id="bert-large"),
])
def test_flash_attention_fwd_bwd_compiles(chip, shape, causal):
    """Forward + backward under the block pairs the defaults pick:
    ``chip_smoke.py``'s 12 heads, ``gpt2-medium.pretrain``'s own shape,
    and BERT-large's rows (not causal: every tile plain)."""
    q = chip(shape, BF16)

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: FA.flash_attention(
                q, k, v, causal=causal, interpret=False).astype(F32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = chip.compile(grads, q, q, q).as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    # one 1024 x 1024 backward tile a row asks for its VMEM (the tile's
    # s, p, dp and ds), BERT's 512 x 512 fits the default
    limit = FA._bwd_vmem_limit(
        shape[1], 64, 2, *FA._bwd_block_sizes(shape[1], shape[1], causal))
    assert (limit is None) == (shape[1] == 512)


def test_flash_attention_gqa_8k_fwd_bwd_compiles(chip):
    """LFM2's attention layer at the benchmark's shape: 32 query heads
    on 8 key/value heads of 64, 8192 positions.  The fused backward
    holds a whole row of q, do, lse, delta and dq in VMEM, 37.7 MB
    here, which the compiler refuses under its 16 MB default: the
    kernel asks for what it needs (``_bwd_vmem_limit``)."""
    q, kv = chip((2, 8192, 32, 64), BF16), chip((2, 8192, 8, 64), BF16)

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: FA.flash_attention(
                q, k, v, causal=True, interpret=False).astype(F32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = chip.compile(grads, q, kv, kv).as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert FA._bwd_vmem_limit(
        8192, 64, 2, *FA._bwd_block_sizes(8192, 8192, True)) > 37 << 20


@pytest.mark.parametrize("heads", [
    pytest.param(16, id="moonlight-16b-a3b"),
    pytest.param(32, id="kimi-linear-48b-a3b"),
])
def test_flash_attention_latent_8k_fwd_bwd_compiles(chip, heads):
    """Latent attention at the benchmark's shapes: Moonlight-16B-A3B's 16
    heads and Kimi-Linear-48B-A3B's 32 (un-rotated: the same kernel
    call), keys of 192 (128 + 64), values of 128, 8192 positions, one
    row.  The scores contract over 192, which is no
    multiple of the 128 lanes: q, dq and the dq accumulator take 256
    lanes a position in VMEM, v, do and dv 128; the tensors in HBM keep
    192 and 128.  The backward's whole-row buffers are 54.5 MB here."""
    q = chip((1, 8192, heads, 192), BF16)
    v = chip((1, 8192, heads, 128), BF16)

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: FA.flash_attention(
                q, k, v, causal=True, interpret=False).astype(F32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    compiled = chip.compile(grads, q, q, v)
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    # nothing in HBM is padded to the lanes: no 256-wide array
    assert "8192,256]" not in text and f"8192,{heads},256]" not in text
    dq, dk, dv = compiled.out_info
    assert (dq.shape, dk.shape, dv.shape) == (q.shape, q.shape, v.shape)
    # the head count is in the autotune and gauge key, not in the VMEM
    # the backward asks for
    assert FA._shape_sig((1, heads, 8192, 192), 8192, True, 128) \
        == f"b1h{heads}sq8192sk8192d192v128c1"
    limit = FA._bwd_vmem_limit(
        8192, 192, 2, *FA._bwd_block_sizes(8192, 8192, True), dv=128)
    assert 54 << 20 < limit < 128 << 20


def test_sliding_window_flash_pair_8k_compiles(chip):
    """Mellum2's sliding-window layer at the benchmark's shape: 32 query
    heads on 4 key/value heads of 128, 8192 positions, a window of 1024,
    one row.  The two kernels carry their own names (the accepted
    ``flash_attention_roofline.train`` reads the full layers alone), the
    backward's grid walks a k block's band and not the row, and its
    whole-row buffers ask for the VMEM the plain call's ask for."""
    q, kv = chip((1, 8192, 32, 128), BF16), chip((1, 8192, 4, 128), BF16)

    def grads(window):
        def fn(q, k, v):
            return jax.grad(
                lambda q, k, v: FA.flash_attention(
                    q, k, v, causal=True, window=window,
                    interpret=False).astype(F32).sum(),
                argnums=(0, 1, 2))(q, k, v)
        return fn

    text = chip.compile(grads(1024), q, kv, kv).as_text()
    assert "flash_window_fwd" in text and "flash_window_bwd" in text
    assert "flash_attention_fwd" not in text
    assert "flash_attention_bwd" not in text
    # a window no shorter than the keys is the plain causal pair
    text = chip.compile(grads(8192), q, kv, kv).as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "flash_window_fwd" not in text
    assert "flash_window_bwd" not in text
    bq, bk = FA._bwd_block_sizes(8192, 8192, True, 1024)
    assert FA._bwd_q_steps(window=1024, sq=8192, sk=8192, bq=bq, bk=bk) \
        == (bk + 1024 - 2) // bq + 1 < 8192 // bq
    assert FA._bwd_vmem_limit(8192, 128, 2, bq, bk) > 37 << 20


def test_laguna_flash_pairs_8k_compile(chip):
    """Laguna-XS.2's two attention calls at the benchmark's shapes, one
    row of 8192 positions over 8 key/value heads of 128: the window
    layers' 64 query heads (a group of 8) under a window of 512, at the
    window path's defaults and at the narrower blocks the chip was read
    at, and the full layers' 48 (a group of 6) through the plain
    pair."""
    kv = chip((1, 8192, 8, 128), BF16)

    def grads(window, blocks=None):
        def fn(q, k, v):
            return jax.grad(
                lambda q, k, v: FA.flash_attention(
                    q, k, v, causal=True, window=window, blocks=blocks,
                    bwd_blocks=blocks,
                    interpret=False).astype(F32).sum(),
                argnums=(0, 1, 2))(q, k, v)
        return fn

    q = chip((1, 8192, 64, 128), BF16)
    for blocks in (None, (256, 512), (256, 256)):
        text = chip.compile(grads(512, blocks), q, kv, kv).as_text()
        assert "flash_window_fwd" in text and "flash_window_bwd" in text
        assert "flash_attention_fwd" not in text
    bq, bk = FA._bwd_block_sizes(8192, 8192, True, 512)
    assert FA._bwd_q_steps(window=512, sq=8192, sk=8192, bq=bq, bk=bk) \
        == (bk + 512 - 2) // bq + 1 < 8192 // bq
    q = chip((1, 8192, 48, 128), BF16)
    compiled = chip.compile(grads(None), q, kv, kv)
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "flash_window_fwd" not in text
    dq, dk, dv = compiled.out_info
    assert (dq.shape, dk.shape, dv.shape) == (q.shape, kv.shape, kv.shape)
    assert FA._shape_sig((1, 64, 8192, 128), 8192, True, 128, 512) \
        == "b1h64sq8192sk8192d128c1w512"


# a 134 MB float32 tensor of the KDA layer laid out again: heads on the
# sublanes ([B, S, H, d] as XLA tiles it) where the projections and the
# kernels hold 8 positions to a tile ([B, S, H * d])
def _retiled(text):
    """The entry computation's own copies, reshapes and broadcasts of
    that size (inside a fusion a broadcast writes nothing)."""
    return re.findall(
        r"= f32\[(?:1024,8,32,128|1,8192,4096|8192,32,128)\]\S* "
        r"(?:copy|reshape|broadcast)\(", text[text.index("\nENTRY "):])


@pytest.mark.parametrize("chunk", [64, 128])
def test_kda_chunk_fwd_bwd_compiles(chip, chunk):
    """The gated delta rule's two kernels at Kimi-Linear-48B-A3B's shape
    (32 heads of 128, 8192 positions, one row, bfloat16 operands and a
    float32 decay, [B, S, H * d] as the projections write them and four
    dimensions by a reshape): the sub-blocks' single-row slices, the
    transposed products, the ``HIGHEST`` products of the triangular
    inverse and the blocks cut from the [B, S, H * d] layout all pass
    the chip's compiler; chunk 128 is the configuration's."""
    from paddle_tpu.ops.pallas import kda
    x = chip((1, 8192, 32 * 128), BF16)
    g, beta = chip((1, 8192, 32 * 128), F32), chip((1, 8192, 32), F32)

    def grads(q, k, v, g, beta):
        def loss(q, k, v, g, beta):
            q, k, v, g = (a.reshape(1, 8192, 32, 128) for a in (q, k, v, g))
            return kda.kda_chunk(q, k, v, g, beta, chunk=chunk,
                                 how="pallas").astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    compiled = chip.compile(grads, x, x, x, g, beta)
    text = compiled.as_text()
    assert "kda_chunk_fwd" in text and "kda_chunk_bwd" in text
    # the decay's running sum is the kernels' own: XLA holds no window
    # scan and no decay cut [B, S / chunk, chunk, H, d] for one
    assert "reduce-window" not in text
    assert f"f32[1,{8192 // chunk},{chunk},32,128]" not in text
    # the per-head sums and spreads round the kernels are products on
    # [B, S, H * d] (PR 43): nothing is laid out again for one (the
    # parent held 6 copies, 6 reshapes and 6 broadcasts here)
    assert not _retiled(text)
    assert [o.shape for o in compiled.out_info] == [
        x.shape, x.shape, x.shape, g.shape, beta.shape]
    assert [o.dtype for o in compiled.out_info] == [BF16] * 3 + [F32] * 2


def _layer_step(chip, monkeypatch, layer, hidden):
    """``layer`` (one operator of a decoder layer, AMP O2) forward +
    recompute + backward at one row of 8192 under the cells' recompute
    policy, as a pure function of its input and parameters, compiled
    for the chip with the kernels steered on (not their XLA form and
    not interpreted)."""
    import paddle_tpu as paddle
    from paddle_tpu.core import scope
    from paddle_tpu.distributed.fleet.pipeline import functional_call
    from paddle_tpu.distributed.fleet.recompute import _POLICIES
    layer = paddle.amp.decorate(layer, level="O2", dtype="bfloat16")
    vals = {n: chip(p._data.shape, p._data.dtype)
            for n, p in layer.named_parameters()}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def block(x, vals):
        # as a ``to_static`` step's replay: a captured program
        with scope.capture(), paddle.amp.auto_cast(level="O2",
                                                   dtype="bfloat16"):
            return functional_call(layer, vals, x)

    block = jax.checkpoint(
        block, policy=_POLICIES["dots_and_kernels_saveable"])
    return chip.compile(
        jax.grad(lambda x, vals: block(x, vals).astype(F32).sum(),
                 argnums=(0, 1)), chip((1, 8192, hidden), BF16), vals)


def test_kimi_delta_attention_fwd_bwd_compiles(chip, monkeypatch):
    """``KimiDeltaAttention`` at the fourth cell's widths (hidden 2304,
    32 heads of 128, one row of 8192, chunk 128), forward + backward
    under the cell's recompute policy and AMP O2, as a pure function of
    its input and parameters: round the two kernels no 134 MB float32
    tensor is laid out again, in the forward, in the recompute or in
    the backward, and the program holds no more than the parent's did
    (``_archive/pr43_kda_glue_ops.py`` prints both sides)."""
    from paddle_tpu.models.kimi_linear import (KimiDeltaAttention,
                                               KimiLinearConfig)
    compiled = _layer_step(chip, monkeypatch, KimiDeltaAttention(
        KimiLinearConfig(hidden_size=2304, num_heads=32, kda_head_dim=128,
                         kda_chunk=128)), 2304)
    text = compiled.as_text()
    assert "kda_chunk_fwd" in text and "kda_chunk_bwd" in text
    assert not _retiled(text)
    # the parent's (PR 42's commit) read 2,291,551,232 here on 2026-10-03
    # with 9 + 4 copies, 10 reshapes and 11 broadcasts, this tree
    # 2,219,611,136.  One layer in one program cannot show what a
    # policy keeps from forward to backward over a whole step:
    # tests/test_kda.py holds the spreads out of the residuals
    assert compiled.memory_analysis().temp_size_in_bytes <= 2_291_551_232


def _kernel_calls(text, name):
    """The program's calls of the Pallas kernel ``name``."""
    return len(re.findall(
        rf" custom-call\([^\n]*tpu_custom_call[^\n]*{name}", text))


def test_mellum_attention_rotates_by_the_kernel_once_each_way(
        chip, monkeypatch):
    """Laguna's window layer (``MellumAttention``: 64 query heads on 8
    of 128, hidden 2048, a window of 512) at the cell's 8192 positions:
    q and k are each rotated by one kernel call forward and one backward,
    the rotated heads are what the policy keeps (no call in the
    recompute), nothing is laid out as the float32 halves the jnp form
    split a head into, and the program holds no more than the parent's
    (``_archive/pr45_rope_ops.py`` prints both sides)."""
    from paddle_tpu.models.mellum import (MellumAttention, MellumConfig,
                                          RopeTables)
    cfg = MellumConfig(hidden_size=2048, num_heads=64, num_kv_heads=8,
                       head_dim=128, sliding_window=512)
    compiled = _layer_step(
        chip, monkeypatch,
        MellumAttention(cfg, "sliding_attention", RopeTables(cfg)), 2048)
    text = compiled.as_text()
    assert _kernel_calls(text, "rope_half_turn_fwd") == 2
    assert _kernel_calls(text, "rope_half_turn_bwd") == 2
    assert _kernel_calls(text, "flash_window_fwd") == 1
    assert _kernel_calls(text, "flash_window_bwd") == 1
    for halves in ("f32[1,8192,64,64]", "f32[1,8192,64,128]",
                   "f32[1,8192,8,64]"):
        assert f"= {halves}" not in text
    # the parent's (PR 44's commit) read 1,630,067,200 here on 2026-10-04
    # (21 + 32 + 21 results of those three shapes), this tree
    # 1,479,330,304
    assert compiled.memory_analysis().temp_size_in_bytes <= 1_630_067_200


def test_lfm2_attention_keeps_the_jnp_rotation(chip, monkeypatch):
    """LFM2's heads are 64 wide, two to a vreg: ``_rotate`` is the jnp
    form there on the chip too, and the layer compiles without the
    kernel."""
    from paddle_tpu.models.lfm2 import Lfm2Attention, Lfm2MoeConfig
    compiled = _layer_step(
        chip, monkeypatch, Lfm2Attention(Lfm2MoeConfig(
            hidden_size=2048, num_heads=32, num_kv_heads=8)), 2048)
    text = compiled.as_text()
    assert "rope_half_turn" not in text
    assert _kernel_calls(text, "flash_attention_fwd") == 1
    assert "= f32[1,8192,32,32]" in text    # the halves of a head of 64


def test_sparse_moe_grouped_products_compile(chip):
    """The dropless block's share of LFM2-24B-A2B (8 of 64 experts,
    2048 -> 1536, top-4) over 16384 tokens, forward and backward: the
    chip's compiler lowers ``ragged_dot`` to its own grouped-product
    kernel, with no product of every token with every expert; the row
    work (gathers, selects, products, weighted sums) is inside
    conditionals, a chunk of the sorted slots each, and nothing is as
    large as all 65536 slot rows."""
    import functools
    import re

    from paddle_tpu.incubate.distributed.models.moe import sparse_moe
    n, h, i, held, router = 16384, 2048, 1536, 8, 64
    fn = functools.partial(sparse_moe, top_k=4, expert_offset=0)

    def grads(x, gate, w1, w3, w2, bias):
        return jax.value_and_grad(
            lambda *a: fn(*a, bias=bias)[0].astype(F32).sum(),
            argnums=(0, 1, 2, 3, 4))(x, gate, w1, w3, w2)

    compiled = chip.compile(
        grads, chip((n, h), BF16), chip((h, router), BF16),
        chip((held, h, i), BF16), chip((held, h, i), BF16),
        chip((held, i, h), BF16), chip((router,), F32))
    text = compiled.as_text()
    assert "ragged-dot" in text
    # a forward and a backward loop, each chunk under its condition
    assert len(re.findall(r" conditional\(", text)) >= 2
    assert not re.search(rf"\[{4 * n},{h}\]|\[{n},4,{h}\]", text)
    # three products forward, the chunk's recompute, six backward, on
    # at most the 4 * n slots: far under one dense product per expert
    flops = compiled.cost_analysis()["flops"]
    assert flops < 0.5 * held * 9 * 2.0 * (4 * n) * h * i
    # the parent of PR 30 (every gather, select and the weighted sum
    # over all 65536 slot rows) needed 1,748,259,328 bytes of
    # temporaries for the same call on the same described chip
    assert compiled.memory_analysis().temp_size_in_bytes <= 1_748_259_328


# rows a chunk, held experts, hidden width, an expert's width
SPARSE_CELLS = {"lfm2": (8192, 8, 2048, 1536),
                "kimi_linear": (8192, 8, 2304, 1024),
                "laguna": (16384, 32, 2048, 512),
                "moonlight": (8192, 8, 2048, 1408),
                "mellum2": (16384, 8, 2304, 896)}


@pytest.mark.parametrize("cell", list(SPARSE_CELLS))
def test_grouped_matmul_kernels_compile_at_the_cells_shapes(chip, cell):
    """``grouped_dot`` and its ``jax.vjp`` at a sparse cell's chunk, up
    ([M, H] x [G, H, I]) and down ([M, I] x [G, I, H]), with the
    committed tile rule: the three kernels, no ``ragged-dot``, and
    what Mosaic or the VMEM limit refuses fails here."""
    from paddle_tpu.ops.pallas import grouped_matmul as GM
    m, g, h, i = SPARSE_CELLS[cell]

    def both(x, w, sizes, dy):
        y, back = jax.vjp(
            lambda x, w: GM.grouped_dot(x, w, sizes, interpret=False), x, w)
        return (y,) + back(dy)

    for k, n in ((h, i), (i, h)):
        text = chip.compile(both, chip((m, k), BF16), chip((g, k, n), BF16),
                            chip((g,), jnp.int32),
                            chip((m, n), BF16)).as_text()
        for name in ("fwd", "dx", "dw"):
            assert _kernel_calls(text, f"grouped_matmul_{name}") == 1
        assert "ragged-dot" not in text


def test_expert_rows_by_the_kernels_hold_no_ragged_dot(chip, monkeypatch):
    """A chunk of Mellum2's cell (16384 slots, 8 experts 2304 -> 896)
    through ``_expert_rows`` and its ``jax.vjp`` on the kernel path:
    three products forward and a rows' and a weights' gradient for
    each, every one a kernel of ``ops/pallas/grouped_matmul.py``, no
    ``ragged-dot`` custom call, and no select over the chunk's rows."""
    from paddle_tpu.incubate.distributed.models.moe import _expert_rows
    m, g, h, i = SPARSE_CELLS["mellum2"]
    # the kernels compiled, not interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def both(rows, w, w1, w3, w2, sizes, mine, dy):
        y, back = jax.vjp(
            lambda *a: _expert_rows(*a, sizes, mine, True),
            rows, w, w1, w3, w2)
        return (y,) + back(dy)

    text = chip.compile(
        both, chip((m, h), BF16), chip((m,), F32), chip((g, h, i), BF16),
        chip((g, h, i), BF16), chip((g, i, h), BF16), chip((g,), jnp.int32),
        chip((m,), jnp.bool_), chip((m, h), F32)).as_text()
    for name in ("fwd", "dx", "dw"):
        assert _kernel_calls(text, f"grouped_matmul_{name}") == 3
    assert "ragged-dot" not in text


def test_fused_adamw_master_weights_compiles(chip):
    n = 124_475_904 // 1024 * 1024          # GPT-124M's parameters, flat
    spec = FO.UpdateSpec(kind="adamw", decay=0.01, use_master=True)
    low, full, scalar = chip((n,), BF16), chip((n,), F32), chip((), F32)

    def update(w, g, master, m, v, lr, b1p, b2p):
        return FO.fused_update(spec, w=w, g=g, lr=lr, master=master, m=m,
                               v=v, b1p=b1p, b2p=b2p, impl="pallas")

    compiled = chip.compile(update, low, low, full, full, full, scalar,
                            scalar, scalar)
    assert "fused_optimizer" in compiled.as_text()
    # updated in place: no second copy of the 124M-element state
    assert compiled.memory_analysis().temp_size_in_bytes < n


def test_fused_residual_layer_norm_fwd_bwd_compiles(chip):
    x, w = chip((8 * 1024, 768), BF16), chip((768,), F32)

    def grads(x, y, w, b):
        def loss(x, y, w, b):
            res, normed = FRN.fused_residual_layer_norm(
                x, y, w, b, interpret=False)
            return (res.astype(F32).sum() + normed.astype(F32).sum())
        return jax.grad(loss, argnums=(0, 1, 2, 3))(x, y, w, b)

    assert _has_kernel(chip.compile(grads, x, x, w, w))


# ----------------------------- off the default path: repaired, or refused
def test_flash_attention_segment_ids_compiles(chip):
    """Was refused for a (1, 512) block of an (8, 1024) array; the ids
    now enter as a column for q and a row for kv."""
    q, seg = chip((8, 1024, 12, 64), BF16), chip((8, 1024), jnp.int32)

    def grads(q, k, v, seg):
        return jax.grad(
            lambda q, k, v: FA.flash_attention(
                q, k, v, causal=True, interpret=False,
                segment_ids=seg).astype(F32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    assert _has_kernel(chip.compile(grads, q, q, q, seg))


@pytest.mark.xfail(strict=True, reason=(
    "int8 KV: 'The Pallas TPU lowering currently requires that the "
    "last two dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the "
    "overall array' — the (1, page_size) window of the scale pools.  "
    "Before the windows it was 'infer-vector-layout: unsupported shape "
    "cast (32,16)->(512,1)'"))
def test_paged_attention_int8_kv_compiles(chip):
    _paged(chip, "ragged", 12, 12, 64, F32, pool_dtype=jnp.int8,
           scales=True)


@pytest.mark.parametrize("option", sorted(P.TPU_REFUSED))
def test_engine_refuses_on_tpu_what_the_compiler_refuses(
        option, monkeypatch, serving_gpt):
    """Every xfail above that an engine option reaches is a coded error
    there on a TPU, not a failure deep inside a compile."""
    from paddle_tpu.core.errors import UnimplementedError
    from paddle_tpu.inference import ContinuousBatchingEngine
    monkeypatch.setattr(P, "use_interpret", lambda: False)
    with pytest.raises(UnimplementedError, match="PDT-E009"):
        ContinuousBatchingEngine(serving_gpt, max_slots=2, page_size=8,
                                 max_seq_len=32, **{option: True})
