#!/usr/bin/env python
"""Start-up proof on the chip: the trainer takes a few steps and the
serving engine answers a few requests, both at GPT-124M's published
width, through the entry points a user calls.

    python chip_smoke.py             one chip: train, then serve
    python chip_smoke.py --chips 4   four chips: the dp2 x mp2 train step
                                     and the mesh engine, each against
                                     its one-chip twin, and nothing else

One process: it imports JAX once and starts no child, because a chip
belongs to one process at a time.  It exits non-zero where
``jax.devices()[0].platform`` is not ``"tpu"`` and never sets a
platform.  Every phase prints one JSON line as it ends; a phase that
raises ends the run non-zero.  The last line is the device it ran on.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

import numpy as np

# GPT-124M (GPT-2 small's widths, vocabulary padded to a multiple of 128)
WIDTH = dict(vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12)
BATCH, SEQ, TRAIN_STEPS = 8, 1024, 4
PROMPT_LENS = (37, 150, 333, 512, 901, 1200)
NEW_TOKENS = 48
PAGE_SIZE = 16
LATE = 2            # requests added while the engine is already running


class _Compiles:
    """Programs the backend produced, from JAX's own monitoring events:
    ``programs`` counts every executable obtained (compiled or read
    back from the persistent cache), ``seconds`` is what that took."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.programs, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.programs += 1
            self.seconds += secs

    def mark(self):
        return self.programs, self.seconds


def _phase(name, compiles, fn):
    """Run one phase; print its JSON line (name, seconds, compile
    seconds, what it checked)."""
    p0, s0 = compiles.mark()
    t0 = time.perf_counter()
    checked = fn()
    p1, s1 = compiles.mark()
    print(json.dumps({
        "phase": name, "seconds": round(time.perf_counter() - t0, 3),
        "compile_seconds": round(s1 - s0, 3), "programs": p1 - p0,
        "checked": checked}), flush=True)


def _kernels(text):
    """Mosaic kernels in a compiled program's text, by kernel name."""
    import re
    names = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        key = (m.group(1).removesuffix("/pallas_call").rsplit("/", 1)[-1]
               if m else "?")
        names[key] = names.get(key, 0) + 1
    return names


def _need(kernels, *fragments):
    for frag in fragments:
        if not any(frag in k for k in kernels):
            raise AssertionError(
                f"no Mosaic custom call matching {frag!r} in the compiled "
                f"program; it holds {sorted(kernels)}")


def _static_text(static_fn, args):
    """Compiled text of a ``jit.to_static`` function's one executable
    (a second ``compile()`` of the same lowering: a read from the
    persistent cache)."""
    (exe,) = static_fn._cache.values()
    vals = [t._read() for t in args] + [t._read() for t in exe.capt_state]
    return exe, exe.compiled.lower(*vals).compile().as_text()


# ----------------------------------------------------------------- train
def _train_model(width, seq, **cfg_kw):
    """GPT as the gpt2-medium cell trains it (perf/models/): AMP O2 bf16
    with master weights, recompute, AdamW — and the ``jit.to_static``
    step over it."""
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(max_seq_len=seq, dropout=0.0, recompute=True,
                    recompute_policy="dots_and_kernels_saveable",
                    **width, **cfg_kw)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = amp.decorate(models=model, optimizers=opt, level="O2",
                              dtype="bfloat16", master_weight=True)

    @paddle.jit.to_static
    def train_step(ids, labels):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return cfg, model, opt, train_step


def _batch(seed, vocab, batch, seq):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, vocab, (batch, seq)).astype(np.int32),
            rng.integers(0, vocab, (batch, seq)).astype(np.int32))


def run_train(seed, width=WIDTH, batch=BATCH, seq=SEQ, on_chip=True):
    """4 steps of the ``jit.to_static`` AdamW step on one fixed batch,
    then ``Model.fit(window=2, num_iters=4)`` on the same width."""
    import paddle_tpu as paddle

    cfg, _, _, train_step = _train_model(width, seq)
    ids, labels = (paddle.to_tensor(a) for a in _batch(
        seed, cfg.vocab_size, batch, seq))
    losses = [float(train_step(ids, labels)) for _ in range(TRAIN_STEPS)]
    uniform = math.log(cfg.vocab_size)
    assert all(math.isfinite(x) for x in losses), losses
    assert abs(losses[0] - uniform) < 0.5, (losses[0], uniform)
    assert losses[-1] < losses[0], losses
    exe, text = _static_text(train_step, (ids, labels))
    assert exe.compiled._cache_size() == 1, exe.compiled._cache_size()
    kernels = _kernels(text)
    if on_chip:
        _need(kernels, "flash_attention_fwd", "flash_attention_bwd",
              "fused_optimizer")

    # the delivered path the README names: hapi Model.prepare / fit
    class Tokens(paddle.io.Dataset):
        def __init__(self):
            self.ids, self.labels = _batch(
                seed + 1, cfg.vocab_size, 4 * batch, seq)

        def __len__(self):
            return len(self.ids)

        def __getitem__(self, i):
            return self.ids[i], self.labels[i]

    _, net, fit_opt, _ = _train_model(width, seq)
    fit_losses = []

    class Rec(paddle.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            fit_losses.append(float(np.asarray(logs["loss"]).reshape(-1)[0]))

    def lm_loss(logits, labels):
        return paddle.nn.functional.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]).astype("float32"),
            labels.reshape([-1]))

    m = paddle.Model(net)
    m.prepare(fit_opt, lm_loss, amp_configs={"level": "O2"})
    m.fit(Tokens(), batch_size=batch, shuffle=False, verbose=0,
          window=2, num_iters=4, callbacks=[Rec()])
    assert len(fit_losses) == 4, fit_losses
    assert all(math.isfinite(x) for x in fit_losses), fit_losses
    return {"losses": losses, "ln_vocab": round(uniform, 4),
            "step_programs": len(train_step._cache), "kernels": kernels,
            "fit_losses": fit_losses}


# ----------------------------------------------------------------- serve
def _serve_model(width, max_seq_len):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(max_seq_len=max_seq_len, dropout=0.0, **width)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return cfg, model


def _prompts(seed, vocab, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lens]


def _engine(model, lens, new, **kw):
    from paddle_tpu.inference import ContinuousBatchingEngine
    need = max(lens) + new
    pages = 1 + sum(-(-(n + new) // PAGE_SIZE) for n in lens)
    return ContinuousBatchingEngine(
        model, max_slots=4, page_size=PAGE_SIZE, decode_window=16,
        max_seq_len=need, total_pages=pages, **kw)


def _spy_programs(eng, seen):
    """Record the arguments the engine's own programs are called with,
    so that their compiled text can be read afterwards."""
    import jax

    def avals(args):
        return jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(
                v.shape, v.dtype, sharding=getattr(v, "sharding", None)),
            args)

    get_mixed, get_window = eng._get_mixed_fn, eng._get_window_runner

    def mixed():
        fn = get_mixed()

        def call(*args):
            seen.setdefault("mixed", (fn, args))
            return fn(*args)
        return call

    def window(k):
        runner = get_window(k)

        def call(*args):
            seen.setdefault("window", (runner, avals(args)))
            return runner(*args)
        return call

    eng._get_mixed_fn, eng._get_window_runner = mixed, window


def _serve_six(eng, prompts, new, compiles=None):
    """Four requests, a few steps, then the other two while the engine
    is running; drain.  Returns the completions in request order and
    the programs the backend produced meanwhile."""
    rids = [eng.add_request(p, new) for p in prompts[:-LATE]]
    done = {}
    p0 = compiles.mark()[0] if compiles else 0
    for _ in range(3):
        for c in eng.step():
            done[c.request_id] = c
    rids += [eng.add_request(p, new) for p in prompts[-LATE:]]
    done.update(eng.run())
    produced = (compiles.mark()[0] - p0) if compiles else 0
    return [done[r] for r in rids], produced


def run_serve(seed, compiles, width=WIDTH, lens=PROMPT_LENS,
              new=NEW_TOKENS, on_chip=True):
    from paddle_tpu.models import generate

    cfg, model = _serve_model(width, 2048 if on_chip else max(lens) + new)
    prompts = _prompts(seed, cfg.vocab_size, lens)
    eng = _engine(model, lens, new)
    seen = {}
    _spy_programs(eng, seen)
    # warm-up, twice over: a to_static program runs eagerly the first
    # time it is called and compiles the second.  Each pass goes through
    # a prefill step, a decode step and one whole decode window.
    for _ in range(2):
        eng.add_request(prompts[0][:20], eng.decode_window + 2)
        eng.run()
    outs, produced = _serve_six(eng, prompts, new, compiles)
    assert produced == 0, f"{produced} programs compiled after warm-up"
    for c in outs:
        assert c.finish_reason == "length", (c.request_id, c.finish_reason)
        assert c.tokens.size == new, (c.request_id, c.tokens.size)
    assert eng.stats["mixed_steps"] > 3, eng.stats   # two admitted later

    fn, args = seen["mixed"]
    _, mixed_text = _static_text(fn, args)
    runner, wargs = seen["window"]
    window_text = runner.lower(*wargs).compile().as_text()
    kernels = {"mixed": _kernels(mixed_text),
               "window": _kernels(window_text)}
    if on_chip:
        _need(kernels["mixed"], "ragged_paged_attention")
        _need(kernels["window"], "ragged_paged_attention")

    agree = []
    for p, c in zip(prompts, outs):
        ref = generate(model, p[None, :], max_new_tokens=new).numpy()[0]
        ref = np.asarray(ref[p.size:], np.int32)
        assert ref[0] == c.tokens[0], (p.size, int(ref[0]), int(c.tokens[0]))
        agree.append(round(float((ref == c.tokens).mean()), 4))
    pool = eng._caches[0]._read()
    return {"requests": len(outs), "new_tokens": new,
            "programs_after_warmup": produced, "kernels": kernels,
            "first_token_equal": True, "agree_share": agree,
            "pool_shape": list(pool.shape),
            "pool_layout": str(getattr(pool, "format", None)),
            "stats": {k: eng.stats[k] for k in (
                "steps", "mixed_steps", "decode_dispatches",
                "tokens_generated", "peak_pages_in_use")}}


# ------------------------------------------------------------ four chips
def run_hybrid(seed, width=WIDTH, batch=BATCH, seq=SEQ, steps=3):
    """The dp2 x mp2 GPT train step of ``__graft_entry__`` on the four
    chips, against the same step on ``jax.devices()[0]``.  Attention is
    XLA's in both: the chip's compiler refuses a Mosaic kernel inside a
    program it partitions ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map" — ROADMAP S7)."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.gpt import shard_gpt

    ids_np, labels_np = _batch(seed, width["vocab_size"], batch, seq)

    def steps_on(mesh):
        _, model, _, train_step = _train_model(
            width, seq, use_flash_attention=False)
        if mesh is not None:
            shard_gpt(model, mesh, dp_axis="dp", mp_axis="mp")
        if mesh is None:
            ids, labels = paddle.to_tensor(ids_np), paddle.to_tensor(
                labels_np)
        else:
            pl = [dist.Shard(0), dist.Replicate()]
            ids = dist.shard_tensor(ids_np, mesh, pl)
            labels = dist.shard_tensor(labels_np, mesh, pl)
        losses = [float(train_step(ids, labels)) for _ in range(steps)]
        assert len(train_step._cache) == 1
        return model, losses

    mesh = dist.ProcessMesh(np.arange(4).reshape(2, 2), ["dp", "mp"])
    model, mesh_losses = steps_on(mesh)
    shardings = {}
    for name, p in model.named_parameters():
        v = p._read()
        spec = str(getattr(v.sharding, "spec", v.sharding))
        shardings.setdefault(
            f"{spec} on {len(v.sharding.device_set)} devices", []).append(
                name)
    w = model.gpt.blocks[0].attn.qkv.weight._read()
    assert "mp" in str(getattr(w.sharding, "spec", "")), w.sharding
    assert len(w.sharding.device_set) == 4, w.sharding
    del model
    _, one_losses = steps_on(None)
    diff = [abs(a - b) for a, b in zip(mesh_losses, one_losses)]
    assert all(math.isfinite(x) for x in mesh_losses + one_losses)
    assert max(diff) < 0.05, (mesh_losses, one_losses)   # bf16 tolerance
    return {"mesh_losses": mesh_losses, "one_chip_losses": one_losses,
            "max_abs_diff": max(diff), "bf16_tolerance": 0.05,
            "param_shardings": {k: (len(v), v[:3])
                                for k, v in shardings.items()},
            "devices": [str(d) for d in jax.devices()]}


def run_mesh_engine(seed, width=WIDTH, lens=PROMPT_LENS, new=NEW_TOKENS):
    """The engine with ``mesh=`` over all four chips, the same six
    requests as the one-chip engine: first tokens equal."""
    import jax
    from jax.sharding import Mesh

    cfg, model = _serve_model(width, 2048)
    prompts = _prompts(seed, cfg.vocab_size, lens)
    mesh = Mesh(np.asarray(jax.devices()), ("tp",))
    tp_outs, _ = _serve_six(_engine(model, lens, new, mesh=mesh),
                            prompts, new)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    one_outs, _ = _serve_six(_engine(model, lens, new), prompts, new)
    agree = []
    for p, a, b in zip(prompts, tp_outs, one_outs):
        assert a.finish_reason == b.finish_reason == "length"
        assert a.tokens[0] == b.tokens[0], (p.size, a.tokens[0], b.tokens[0])
        agree.append(round(float((a.tokens == b.tokens).mean()), 4))
    return {"requests": len(tp_outs), "first_token_equal": True,
            "agree_share_vs_one_chip": agree,
            "bytes_in_use_with_mesh_engine": in_use}


# ------------------------------------------------------------------ main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phases and what "
                         "they are compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, batches and prompts")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: jax.devices()[0].platform is "
              f"{dev.platform!r}, not 'tpu': nothing was run",
              file=sys.stderr)
        return 1
    if args.chips == 4 and jax.device_count() != 4:
        print(f"chip_smoke: --chips 4 but jax sees "
              f"{jax.device_count()} device(s)", file=sys.stderr)
        return 1

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    compiles = _Compiles()
    print(json.dumps({"compile_cache": cache_dir, "seed": args.seed,
                      "chips": args.chips}), flush=True)
    if args.chips == 1:
        _phase("train", compiles, lambda: run_train(args.seed))
        _phase("serve", compiles, lambda: run_serve(args.seed, compiles))
    else:
        _phase("hybrid_train", compiles, lambda: run_hybrid(args.seed))
        _phase("mesh_engine", compiles, lambda: run_mesh_engine(args.seed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
