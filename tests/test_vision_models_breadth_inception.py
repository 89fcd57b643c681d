"""The two Inception networks of the round-3 vision batch, at the input
sizes they need (tests/test_vision_models_breadth.py has the rest at 64
pixels; the halves share nothing, and together they were over 200 s of
tier-1)."""
import numpy as np

import _traced
import paddle_tpu as paddle
from paddle_tpu.vision import models


def _fwd(model, size, batch):
    """The eval forward as ONE compiled program (``_traced.forward``)."""
    x = np.random.default_rng(0).normal(
        size=(batch, 3, size, size)).astype("float32")
    return _traced.forward(model, x)


def test_inception_v3_forward():
    paddle.seed(0)
    model = models.inception_v3(num_classes=7)
    out = _fwd(model, size=299, batch=1)
    assert tuple(out.shape) == (1, 7)


def test_googlenet_aux_heads():
    paddle.seed(0)
    model = models.GoogLeNet(num_classes=6)
    out, aux1, aux2 = _fwd(model, size=96, batch=2)
    assert tuple(out.shape) == (2, 6)
    assert tuple(aux1.shape) == (2, 6) and tuple(aux2.shape) == (2, 6)
