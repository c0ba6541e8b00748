"""Classic vision families added for reference parity (ref:
python/paddle/vision/models/{lenet,alexnet,squeezenet,googlenet,
shufflenetv2,inceptionv3}.py): shape checks + a gradient smoke test."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.vision import models as M


def _img(n=2, c=3, s=64):
    rng = np.random.default_rng(0)
    return paddle.to_tensor(rng.standard_normal((n, c, s, s)).astype(
        np.float32))


def _forward(model, x):
    """The whole forward as ONE compiled program, in eval(): what these
    cases assert is a shape, and run eagerly a model is hundreds of
    one-operation programs that time the CPU compiler (each input is the
    smallest the architecture's strides admit, or near it)."""
    model.eval()
    return paddle.jit.to_static(model)(x)


class TestShapes:
    def test_lenet(self):
        x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
            (2, 1, 28, 28)).astype(np.float32))
        out = _forward(M.LeNet(num_classes=10), x)
        assert tuple(out.shape) == (2, 10)

    def test_alexnet(self):
        out = _forward(M.alexnet(num_classes=7), _img(s=63))
        assert tuple(out.shape) == (2, 7)

    @pytest.mark.parametrize("ctor", [M.squeezenet1_0, M.squeezenet1_1])
    def test_squeezenet(self, ctor):
        out = _forward(ctor(num_classes=5), _img(s=32))
        assert tuple(out.shape) == (2, 5)

    def test_googlenet(self):
        out = _forward(M.googlenet(num_classes=6), _img(s=32))
        assert tuple(out.shape) == (2, 6)

    @pytest.mark.parametrize("ctor", [M.shufflenet_v2_x0_25,
                                      M.shufflenet_v2_x1_0])
    def test_shufflenet(self, ctor):
        out = _forward(ctor(num_classes=4), _img(s=32))
        assert tuple(out.shape) == (2, 4)

    def test_inception_v3(self):
        out = _forward(M.inception_v3(num_classes=3), _img(s=75))
        assert tuple(out.shape) == (2, 3)


class TestTraining:
    def test_shufflenet_grads_flow(self):
        m = M.shufflenet_v2_x0_25(num_classes=4)
        out = m(_img(s=64))
        loss = nn.functional.cross_entropy(
            out, paddle.to_tensor(np.array([0, 2])))
        loss.backward()
        missing = [n for n, p in m.named_parameters()
                   if not p.stop_gradient and p.grad is None]
        assert not missing, missing[:5]

    def test_googlenet_channel_count_consistency(self):
        # every inception stage must produce the channel count the next
        # stage consumes — a full forward at a second resolution checks it
        out = _forward(M.googlenet(num_classes=0), _img(s=48))
        assert out.shape[1] == 1024


class TestDetectionOps:
    """New detection-op tail (ref: python/paddle/vision/ops.py yolo_loss,
    prior_box, read_file, RoI layer wrappers, ConvNormActivation)."""

    def _head(self, N=1, M=1, C=2, H=4, W=4, fill=0.0):
        return np.full((N, M * (5 + C), H, W), fill, np.float32)

    def test_yolo_loss_perfect_prediction_smaller_than_wrong(self):
        from paddle_tpu.vision.ops import yolo_loss
        C, H, W, ds = 2, 4, 4, 32
        anchors = [32, 32]          # one anchor == one mask entry
        # one gt centered in cell (1, 1), size = anchor size (tw*=0)
        gw = 32 / (W * ds)
        gt = np.array([[[ (1.5) / W, (1.5) / H, gw, gw ]]], np.float32)
        lbl = np.array([[1]], np.int64)

        x = self._head(C=C, H=H, W=W)
        x_good = x.copy().reshape(1, 1, 5 + C, H, W)
        x_good[0, 0, 4, 1, 1] = 8.0     # confident objectness at the cell
        x_good[0, 0, 5 + 1, 1, 1] = 8.0  # right class
        x_good[0, 0, 5 + 0, 1, 1] = -8.0
        x_good = x_good.reshape(1, -1, H, W)

        x_bad = x.copy().reshape(1, 1, 5 + C, H, W)
        x_bad[0, 0, 4, 1, 1] = -8.0     # no objectness where the gt is
        x_bad[0, 0, 5 + 0, 1, 1] = 8.0  # wrong class
        x_bad = x_bad.reshape(1, -1, H, W)

        args = dict(anchors=anchors, anchor_mask=[0], class_num=C,
                    ignore_thresh=0.7, downsample_ratio=ds,
                    use_label_smooth=False)
        lg = float(yolo_loss(paddle.to_tensor(x_good),
                             paddle.to_tensor(gt), paddle.to_tensor(lbl),
                             **args).numpy()[0])
        lb = float(yolo_loss(paddle.to_tensor(x_bad),
                             paddle.to_tensor(gt), paddle.to_tensor(lbl),
                             **args).numpy()[0])
        assert np.isfinite(lg) and np.isfinite(lb)
        assert lg < lb, (lg, lb)

    def test_yolo_loss_grads_flow(self):
        from paddle_tpu.vision.ops import yolo_loss
        x = paddle.to_tensor(self._head(fill=0.1))
        x.stop_gradient = False
        gt = paddle.to_tensor(np.array([[[0.4, 0.4, 0.2, 0.2]]], np.float32))
        lbl = paddle.to_tensor(np.array([[0]], np.int64))
        loss = yolo_loss(x, gt, lbl, anchors=[32, 32], anchor_mask=[0],
                         class_num=2, ignore_thresh=0.7,
                         downsample_ratio=32)
        loss.sum().backward()
        g = np.asarray(x.grad.numpy())
        assert np.isfinite(g).all() and np.abs(g).sum() > 0

    def test_prior_box(self):
        from paddle_tpu.vision.ops import prior_box
        feat = paddle.ones([1, 8, 4, 4])
        img = paddle.ones([1, 3, 32, 32])
        boxes, var = prior_box(feat, img, min_sizes=[8.0],
                               aspect_ratios=[2.0], clip=True)
        assert tuple(boxes.shape) == (4, 4, 2, 4)
        b = boxes.numpy()
        assert (b >= 0).all() and (b <= 1).all()
        assert tuple(var.shape) == (4, 4, 2, 4)

    def test_read_file(self, tmp_path):
        from paddle_tpu.vision.ops import read_file
        p = tmp_path / "blob.bin"
        p.write_bytes(b"\x01\x02\xff")
        t = read_file(str(p))
        np.testing.assert_array_equal(t.numpy(), [1, 2, 255])

    def test_conv_norm_activation(self):
        from paddle_tpu.vision.ops import ConvNormActivation
        block = ConvNormActivation(3, 8, kernel_size=3)
        out = block(paddle.ones([1, 3, 8, 8]))
        assert tuple(out.shape) == (1, 8, 8, 8)

    def test_roi_layer_wrappers(self):
        from paddle_tpu.vision.ops import RoIAlign
        x = paddle.ones([1, 2, 8, 8])
        boxes = paddle.to_tensor(np.array([[0, 0, 4, 4]], np.float32))
        out = RoIAlign(output_size=2)(x, boxes,
                                      paddle.to_tensor(np.array([1])))
        assert tuple(out.shape) == (1, 2, 2, 2)

    def test_conv_norm_activation_none_disables_norm(self):
        from paddle_tpu.vision.ops import ConvNormActivation
        block = ConvNormActivation(3, 8, norm_layer=None,
                                   activation_layer=None)
        # conv only, with bias (reference semantics for norm_layer=None)
        assert len(list(block.sublayers() if hasattr(block, "sublayers")
                        else block)) >= 1
        out = block(paddle.ones([1, 3, 8, 8]))
        assert tuple(out.shape) == (1, 8, 8, 8)

    def test_roi_wrapper_is_layer(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.vision.ops import RoIAlign
        assert issubclass(RoIAlign, nn.Layer)
