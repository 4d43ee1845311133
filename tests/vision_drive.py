"""The forward-and-backward drive the vision zoo tests share (not a test
module: test_vision*.py import it)."""
import numpy as np

import paddle_tpu as paddle


def drive(net, size=64, classes=5):
    """A batch of two through `net`, then backward from the logits' sum."""
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(2, 3, size, size).astype(np.float32))
    out = net(x)
    assert list(out.shape) == [2, classes]
    out.sum().backward()
    # EVERY trainable param must receive a gradient (a disconnected
    # branch would otherwise pass silently)
    missing = [n for n, p in net.named_parameters()
               if p.trainable and p.grad is None]
    assert not missing, missing
    return out
