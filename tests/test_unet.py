"""SD UNet (BASELINE config 5): conditional denoising forward + training
step on a toy denoising objective."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu.models.unet import UNet2DConditionModel, unet_tiny


def _eval_model():
    """In eval() and as ONE compiled program a forward: run eagerly the
    model is hundreds of one-operation programs, and the cases below
    would time the CPU compiler."""
    paddle.seed(0)
    m = UNet2DConditionModel(unet_tiny())
    m.eval()
    return paddle.jit.to_static(m)


def test_unet_forward_shape():
    m = _eval_model()
    x = paddle.to_tensor(np.random.randn(2, 4, 16, 16).astype(np.float32))
    t = paddle.to_tensor(np.array([1, 999], np.int32))
    ctx = paddle.to_tensor(np.random.randn(2, 8, 64).astype(np.float32))
    out = m(x, t, ctx)
    assert out.shape == [2, 4, 16, 16]


def test_unet_denoising_trains():
    cfg = unet_tiny()
    paddle.seed(0)
    np.random.seed(0)
    m = UNet2DConditionModel(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())

    clean = np.random.randn(2, 4, 16, 16).astype(np.float32)
    noise = np.random.randn(2, 4, 16, 16).astype(np.float32)
    noisy = clean + noise
    ctx = np.random.randn(2, 8, 64).astype(np.float32)
    t = np.array([10, 500], np.int32)

    def step_fn(xb, tb, cb, nb):
        pred = m(xb, tb, cb)
        return F.mse_loss(pred, nb)

    step = paddle.jit.TrainStep(m, o, step_fn)
    args = [paddle.to_tensor(a) for a in (noisy, t, ctx, noise)]
    losses = [step(*args).item() for _ in range(12)]
    assert losses[-1] < losses[0], (losses[0], losses[-1])


def test_unet_cross_attention_uses_context():
    m = _eval_model()
    x = paddle.to_tensor(np.random.randn(1, 4, 16, 16).astype(np.float32))
    t = paddle.to_tensor(np.array([5], np.int32))
    c1 = paddle.to_tensor(np.random.randn(1, 8, 64).astype(np.float32))
    c2 = paddle.to_tensor(np.random.randn(1, 8, 64).astype(np.float32))
    o1 = m(x, t, c1).numpy()
    o2 = m(x, t, c2).numpy()
    assert not np.allclose(o1, o2), "context must influence output"
