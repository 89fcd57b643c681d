"""PR 44: the five accepted cells trace to the same
forward-and-backward jaxpr on both checkouts.

    JAX_PLATFORMS=cpu python _archive/pr44_cells_jaxpr.py _parent .

Per checkout (a child process each) and per cell: the model built by the
cell's adapter at its published widths, its loss as a pure function of
the parameters (``functional_call`` under AMP O2, recompute and all),
``jax.make_jaxpr(jax.grad(...))`` at the cell's batch shape on abstract
values, sha256 of the text with object addresses struck out.  Nothing
is computed."""
import hashlib
import json
import os
import re
import subprocess
import sys

CELLS = ("gpt2-medium.pretrain", "lfm2-24b-a2b.pretrain_8k",
         "moonlight-16b-a3b.pretrain_8k", "kimi-linear-48b-a3b.pretrain_8k",
         "mellum2-12b-a2.5b.pretrain_8k")


def main(root):
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.abspath(root))
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.pipeline import functional_call
    from perf import loader
    assert os.path.abspath(paddle.__file__).startswith(os.path.abspath(root))
    bench = loader.benchmark()
    for cell in CELLS:
        w = next(w for w in bench["workloads"] if w["name"] == cell)
        cfg = loader.data("configs", w["config"])
        batch = loader.data("traffic", w["traffic"])["batch"]
        adapter = loader.module("models", cfg["family"])
        program = adapter.build_train(cfg, batch)
        model, compute = program.model, program.prec["compute"]
        vals = {n: jax.ShapeDtypeStruct(p._data.shape, p._data.dtype)
                for n, p in model.named_parameters()}
        ids = jax.ShapeDtypeStruct((batch["rows"], batch["seq_len"]),
                                   jnp.int32)

        def loss(vals, ids, labels):
            with paddle.amp.auto_cast(level="O2", dtype=compute):
                return functional_call(model, vals, ids, labels)

        text = str(jax.make_jaxpr(jax.grad(loss))(vals, ids, ids))
        text = re.sub(r"0x[0-9a-f]+", "0x", text)
        print(json.dumps({"cell": cell, "equations": text.count(" = "),
                          "sha256": hashlib.sha256(
                              text.encode()).hexdigest()[:16]}), flush=True)
        del program, model


if __name__ == "__main__":
    if os.environ.get("PR44_CHILD"):
        main(sys.argv[1])
    else:
        for root in sys.argv[1:]:
            print(f"#### {root}", flush=True)
            subprocess.run([sys.executable, __file__, root], check=True,
                           env={**os.environ, "PR44_CHILD": "1"})
