"""Vision model zoo: forward shapes + one grad step per family (ref:
test/legacy_test/test_vision_models.py pattern — construct, forward,
check logits shape)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.vision import models as M


def _forward(model):
    """The whole forward as ONE compiled program, in eval(), at the
    smallest input the five stride-2 stages admit: what is asserted is a
    shape, and run eagerly a model is hundreds of one-operation programs
    that time the CPU compiler."""
    model.eval()
    x = paddle.to_tensor(np.random.randn(1, 3, 32, 32).astype(np.float32))
    return paddle.jit.to_static(model)(x)


@pytest.mark.parametrize("ctor", [
    M.vgg11, M.mobilenet_v1, M.mobilenet_v2, M.mobilenet_v3_small,
    M.mobilenet_v3_large, M.densenet121,
], ids=lambda f: f.__name__)
def test_model_forward(ctor):
    paddle.seed(0)
    assert _forward(ctor(num_classes=10)).shape == [1, 10]


def test_vgg_backward():
    paddle.seed(0)
    m = M.vgg11(num_classes=4)
    x = paddle.to_tensor(np.random.randn(2, 3, 32, 32).astype(np.float32))
    loss = F.cross_entropy(m(x), paddle.to_tensor(np.array([0, 1])))
    loss.backward()
    missing = [n for n, p in m.named_parameters()
               if not p.stop_gradient and p.grad is None]
    assert not missing, missing


def test_mobilenet_v2_scale():
    assert _forward(M.mobilenet_v2(scale=0.5, num_classes=5)).shape == [1, 5]
