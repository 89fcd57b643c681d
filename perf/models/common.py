"""What the family adapters share: how a configuration file and the
benchmark's seeded weights become the program's own objects, built the
way a user builds them (``GPTForCausalLM`` / ``BertForPretraining``,
``amp.decorate`` O2, ``AdamW``, ``jit.to_static``; the engine for
serving).  The only files of the benchmark that import the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def unstack(weights, program_name):
    """The reference's leaves (``blocks.*`` stacked over the layers)
    as the program's named parameters: ``program_name(leaf, layer)``
    gives the program's name, ``layer`` None for an unstacked leaf."""
    out = {}
    for name, w in weights.items():
        if name.startswith("blocks."):
            for i in range(w.shape[0]):
                out[program_name(name, i)] = w[i]
        else:
            out[program_name(name, None)] = w
    return out


def load_weights(model, state):
    """Seeded weights into the program's model by its public
    ``set_state_dict``; every parameter must be given and taken."""
    import paddle_tpu as paddle
    missing, unexpected = model.set_state_dict(
        {k: paddle.to_tensor(v) for k, v in state.items()})
    if missing or unexpected:
        raise RuntimeError(f"seeded weights do not match the program's "
                           f"parameters: missing {missing[:5]}, "
                           f"unexpected {unexpected[:5]}")


_norm_and_dots = jax.jit(lambda x, r: (
    jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
    jnp.sum(x.astype(jnp.float32)[None] * r,
            axis=tuple(range(1, r.ndim)))))
_diff_norms = jax.jit(lambda xs, ys: [jnp.sqrt(jnp.sum(jnp.square(
    x.astype(jnp.float32) - y))) for x, y in zip(xs, ys)])


class TrainProgram:
    """One compiled training step with its state: the object that
    set-up drives through the checked first steps and then hands to
    the timed window."""

    def __init__(self, model, prec, loss_call):
        import paddle_tpu as paddle
        import paddle_tpu.amp as amp
        if prec["optimizer"] != "AdamW" or prec["amp_level"] != "O2":
            raise ValueError(f"this adapter builds AdamW under AMP O2, "
                             f"the configuration asks for {prec}")
        self.prec = prec
        # the program's own switch between its flat fused AdamW and its
        # per-parameter update
        paddle.set_flags({"fused_opt": bool(prec["fused_optimizer"])})
        model.train()
        opt = paddle.optimizer.AdamW(
            learning_rate=prec["learning_rate"], beta1=prec["beta1"],
            beta2=prec["beta2"], epsilon=prec["epsilon"],
            weight_decay=prec["weight_decay"],
            parameters=model.parameters())
        self.model, self.opt = amp.decorate(
            models=model, optimizers=opt, level="O2",
            dtype=prec["compute"], master_weight=True)
        model, opt = self.model, self.opt

        @paddle.jit.to_static
        def train_step(*batch):
            with amp.auto_cast(level="O2", dtype=prec["compute"]):
                loss = loss_call(model, *batch)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        self.train_step = train_step
        self._to_tensor = paddle.to_tensor

    def low_leaves(self):
        """Names of the parameters the program holds in the compute
        type (the rest stay float32)."""
        return {n for n, p in self.model.named_parameters()
                if str(p.dtype).endswith(self.prec["compute"])}

    def feed(self, batch):
        return tuple(self._to_tensor(a) for a in batch)

    def step(self, tensors):
        return self.train_step(*tensors)

    def programs(self):
        return len(self.train_step._cache)

    def first_grad_stats(self, table, program_name):
        """Per parameter, the norm and the sketch (its inner products
        with the fixed vectors of ``reference.common.sketch_vectors``)
        of the gradient the optimizer was given in its first step:
        AdamW's first moment after one step is (1 - beta1) times it.
        ``table`` is the reference's leaf table, whose sorted names
        number the sketch vectors; ``program_name`` maps its leaves to
        the program's."""
        from perf.reference import common as C
        sd = self.opt.state_dict()
        moment = {n: sd[f"param_{i}.moment1"]._read() for i, (n, _) in
                  enumerate(self.model.named_parameters())}
        scale = 1.0 - self.prec["beta1"]
        norms, sketches = {}, {}
        for j, leaf in enumerate(sorted(table)):
            r = C.sketch_vectors(table[leaf][0], j)
            layers = (range(table[leaf][0][0])
                      if leaf.startswith("blocks.") else [None])
            for i in layers:
                name = program_name(leaf, i)
                norm, dots = _norm_and_dots(
                    moment[name], r if i is None else r[:, i])
                norms[name] = float(norm) / scale
                sketches[name] = [float(d) / scale for d in dots]
        return norms, sketches

    def param_change_norms(self, initial):
        """Per parameter, the norm of (float32 master weight, or the
        parameter where it has none) minus ``initial[name]``."""
        sd = self.opt.state_dict()
        names, now = [], []
        for i, (n, p) in enumerate(self.model.named_parameters()):
            master = sd.get(f"param_{i}.master_weight")
            names.append(n)
            now.append((master if master is not None else p)._read())
        then = [initial[n] for n in names]
        return dict(zip(names, map(float, jax.device_get(
            _diff_norms(now, then)))))
