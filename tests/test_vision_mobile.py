"""Vision zoo, MobileNetV1/V2/V3 / ShuffleNetV2 / SqueezeNet (reference
vision families): forward and backward through the dygraph tape, every
trainable parameter given a gradient."""
import numpy as np
import pytest

import paddle_tpu as paddle
from vision_drive import drive


class TestNewModelFamilies:
    def test_mobilenet_v2_forward_and_grads(self):
        from paddle_tpu.vision.models import mobilenet_v2
        paddle.seed(0)
        m = mobilenet_v2(num_classes=7)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 3, 64, 64).astype(np.float32))
        out = m(x)
        assert list(out.shape) == [2, 7]
        out.sum().backward()
        assert all(p.grad is not None for p in m.parameters()
                   if p.trainable)

    def test_mobilenet_width_multiplier(self):
        from paddle_tpu.vision.models import MobileNetV2
        m = MobileNetV2(scale=0.5, num_classes=5)
        x = paddle.to_tensor(np.zeros((1, 3, 64, 64), np.float32))
        assert list(m(x).shape) == [1, 5]

    def test_pretrained_raises_honestly(self):
        from paddle_tpu.vision.models import mobilenet_v2
        with pytest.raises(NotImplementedError, match="state_dict"):
            mobilenet_v2(pretrained=True)


class TestDeeperFamilies:
    def test_squeezenet(self):
        from paddle_tpu.vision.models import squeezenet1_1
        paddle.seed(0)
        drive(squeezenet1_1(num_classes=5), size=96)

    def test_shufflenet_v2(self):
        from paddle_tpu.vision.models import shufflenet_v2_x0_5
        paddle.seed(0)
        drive(shufflenet_v2_x0_5(num_classes=5))

    def test_shufflenet_act_validated(self):
        from paddle_tpu.vision.models import ShuffleNetV2
        with pytest.raises(ValueError, match="unsupported activation"):
            ShuffleNetV2(scale=0.5, act="bogus")
        paddle.seed(0)
        drive(ShuffleNetV2(scale=0.25, act="swish", num_classes=5))

    def test_channel_shuffle_is_permutation(self):
        from paddle_tpu.vision.models.shufflenetv2 import _channel_shuffle
        x = paddle.to_tensor(
            np.arange(2 * 8 * 2 * 2, dtype=np.float32)
            .reshape(2, 8, 2, 2))
        y = _channel_shuffle(x, groups=2)
        assert sorted(y.numpy().ravel()) == sorted(x.numpy().ravel())
        assert not np.array_equal(y.numpy(), x.numpy())

    def test_mobilenet_v1(self):
        from paddle_tpu.vision.models import mobilenet_v1
        paddle.seed(0)
        drive(mobilenet_v1(scale=0.5, num_classes=5))

    def test_mobilenet_v3(self):
        from paddle_tpu.vision.models import (mobilenet_v3_small,
                                              mobilenet_v3_large)
        paddle.seed(0)
        drive(mobilenet_v3_small(num_classes=5))
        # large config builds (forward-only: full grad drive is slow)
        net = mobilenet_v3_large(scale=0.5, num_classes=3)
        x = paddle.to_tensor(np.zeros((1, 3, 64, 64), np.float32))
        assert list(net(x).shape) == [1, 3]
