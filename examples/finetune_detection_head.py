"""Detection-head fine-tune on synthetic boxes: MobileNet backbone +
YOLO head trained with paddle.vision.ops.yolo_loss, decoded with
yolo_box, de-duplicated with matrix_nms.

    python examples/finetune_detection_head.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# examples demo on CPU devices by default, at toy sizes
if os.environ.get("PADDLE_TPU_EXAMPLE_BACKEND", "cpu") == "cpu":
    from paddle_tpu.device import pin_cpu
    assert pin_cpu(1), "could not pin the CPU backend"

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.vision import ops

CLASSES = 3
ANCHORS = [16, 16, 32, 32]
MASK = [0, 1]
IMG = 64
DOWNSAMPLE = 16


class TinyDetector(nn.Layer):
    """A small conv backbone + the YOLO head conv."""

    def __init__(self):
        super().__init__()
        self.backbone = nn.Sequential(
            nn.Conv2D(3, 16, 3, 2, 1), nn.ReLU(),
            nn.Conv2D(16, 32, 3, 2, 1), nn.ReLU(),
            nn.Conv2D(32, 32, 3, 2, 1), nn.ReLU(),
            nn.Conv2D(32, 32, 3, 2, 1), nn.ReLU())
        self.head = nn.Conv2D(32, len(MASK) * (5 + CLASSES), 1)

    def forward(self, x):
        return self.head(self.backbone(x))


def synthetic_batch(rng, batch=4):
    """Images with one bright square each; the box is the target."""
    imgs = rng.rand(batch, 3, IMG, IMG).astype(np.float32) * 0.1
    boxes = np.zeros((batch, 1, 4), np.float32)
    labels = np.zeros((batch, 1), np.int64)
    for i in range(batch):
        cx, cy = rng.uniform(0.3, 0.7, 2)
        w = h = rng.uniform(0.2, 0.4)
        x0 = int((cx - w / 2) * IMG)
        y0 = int((cy - h / 2) * IMG)
        x1 = int((cx + w / 2) * IMG)
        y1 = int((cy + h / 2) * IMG)
        cls = rng.randint(0, CLASSES)
        imgs[i, cls, y0:y1, x0:x1] = 1.0
        boxes[i, 0] = [cx, cy, w, h]
        labels[i, 0] = cls
    return imgs, boxes, labels


def main():
    paddle.seed(0)
    rng = np.random.RandomState(0)
    net = TinyDetector()
    opt = paddle.optimizer.Adam(learning_rate=3e-4,
                                parameters=net.parameters())

    losses = []
    for step in range(16):
        imgs, boxes, labels = synthetic_batch(rng)
        pred = net(paddle.to_tensor(imgs))
        loss = ops.yolo_loss(
            pred, paddle.to_tensor(boxes), paddle.to_tensor(labels),
            ANCHORS, MASK, CLASSES, ignore_thresh=0.7,
            downsample_ratio=DOWNSAMPLE, use_label_smooth=False).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
        if step % 4 == 0:
            print(f"step {step}: yolo_loss {losses[-1]:.3f}")
    # robust gate: mean of the last quarter under mean of the first
    head = float(np.mean(losses[:4]))
    tail = float(np.mean(losses[-4:]))
    print(f"loss {head:.3f} -> {tail:.3f}")
    assert tail < head, (head, tail)

    # decode + nms on one image
    imgs, _boxes, _labels = synthetic_batch(rng, batch=1)
    pred = net(paddle.to_tensor(imgs))
    bxs, scores = ops.yolo_box(
        pred, paddle.to_tensor(np.array([[IMG, IMG]], np.int32)),
        [ANCHORS[2 * i + j] for i in MASK for j in (0, 1)], CLASSES,
        conf_thresh=0.0, downsample_ratio=DOWNSAMPLE)
    out, nums = ops.matrix_nms(
        bxs.reshape([1, -1, 4]),
        paddle.to_tensor(np.transpose(scores.numpy(), (0, 2, 1))),
        score_threshold=0.0, post_threshold=0.0, nms_top_k=10,
        keep_top_k=5, background_label=-1)
    print(f"kept {int(nums.numpy()[0])} detections; "
          f"top: {out.numpy()[0][:2]}")
    print("detection example OK")


if __name__ == "__main__":
    main()
