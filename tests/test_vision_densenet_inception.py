"""Vision zoo, DenseNet / GoogLeNet / InceptionV3 (reference vision
families): forward and backward through the dygraph tape, every trainable
parameter given a gradient."""
import numpy as np

import paddle_tpu as paddle
from vision_drive import drive


class TestDeeperFamilies:
    def test_densenet121(self):
        from paddle_tpu.vision.models import densenet121
        paddle.seed(0)
        drive(densenet121(num_classes=5))

    def test_densenet_dropout_applied(self):
        from paddle_tpu.vision.models import DenseNet
        paddle.seed(0)
        m = DenseNet(layers=121, dropout=0.5, num_classes=5)
        # the batch test_densenet121 drives: its layers' programs are reused
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 3, 64, 64).astype(np.float32))
        m.train()
        a = m(x).numpy()
        b = m(x).numpy()
        assert not np.allclose(a, b), "train-mode dropout must be active"
        m.eval()
        c = m(x).numpy()
        d = m(x).numpy()
        np.testing.assert_array_equal(c, d)

    def test_googlenet_triple_output(self):
        from paddle_tpu.vision.models import googlenet
        paddle.seed(0)
        net = googlenet(num_classes=5)
        # aux heads' 1152-wide flatten assumes the canonical 224 input
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 3, 224, 224).astype(np.float32))
        out, aux1, aux2 = net(x)
        assert list(out.shape) == [2, 5]
        assert list(aux1.shape) == [2, 5]
        assert list(aux2.shape) == [2, 5]
        # reference training recipe: main + 0.3*(aux1 + aux2)
        loss = out.sum() + 0.3 * (aux1.sum() + aux2.sum())
        loss.backward()
        missing = [n for n, p in net.named_parameters()
                   if p.trainable and p.grad is None]
        assert not missing, missing

    def test_inception_v3(self):
        from paddle_tpu.vision.models import inception_v3
        paddle.seed(0)
        # stem + ladder downsample by 32+; 96px (the min-ish valid input)
        # keeps CPU time sane
        drive(inception_v3(num_classes=5), size=96)

    def test_inception_v3_param_count(self):
        from paddle_tpu.vision.models import InceptionV3
        net = InceptionV3(num_classes=1000)
        n = sum(int(np.prod(p.shape)) for p in net.parameters())
        # canonical InceptionV3 (no aux head): ~23.8M params
        assert 22e6 < n < 25e6, n
