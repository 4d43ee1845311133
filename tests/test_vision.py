"""Vision pack tests: ResNet/LeNet forward+train, transforms, datasets,
nms/roi_align numerics (reference vision test discipline)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.vision import models, transforms, datasets, ops
from vision_drive import drive


class TestModels:
    def test_resnet18_forward_shape(self):
        net = models.resnet18(num_classes=10)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 3, 64, 64).astype(np.float32))
        net.eval()
        out = net(x)
        assert list(out.shape) == [2, 10]

    def test_resnet50_bottleneck_structure(self):
        net = models.resnet50(num_classes=8)
        # 50-layer: conv1 + 3*3 + 4*3 + 6*3 + 3*3 bottleneck convs + fc
        n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
        assert n_params > 23e6                      # ~23.5M + fc
        x = paddle.to_tensor(np.zeros((1, 3, 32, 32), np.float32))
        net.eval()
        assert list(net(x).shape) == [1, 8]

    def test_lenet_trains_via_hapi(self):
        net = models.LeNet(num_classes=4)
        model = paddle.Model(net)
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        from paddle_tpu.metric import Accuracy
        model.prepare(opt, nn.CrossEntropyLoss(), Accuracy())
        ds = datasets.FakeData(num_samples=64, image_shape=(1, 28, 28),
                               num_classes=4)
        hist = model.fit(ds, epochs=2, batch_size=16, verbose=0)
        assert hist["loss"][-1] < hist["loss"][0]

    def test_pretrained_raises(self):
        with pytest.raises(NotImplementedError, match="state_dict"):
            models.resnet50(pretrained=True)


class TestTransforms:
    def test_compose_to_tensor_normalize(self):
        t = transforms.Compose([
            transforms.ToTensor(),
            transforms.Normalize(mean=[0.5], std=[0.5])])
        img = (np.ones((8, 8), np.uint8) * 255)
        out = t(img)
        np.testing.assert_allclose(out.numpy(), np.ones((1, 8, 8)),
                                   atol=1e-6)

    def test_resize_aspect_and_exact(self):
        img = np.arange(12 * 16, dtype=np.float32).reshape(12, 16)
        out = transforms.Resize((6, 8))(img)
        assert out.shape == (6, 8)
        out2 = transforms.Resize(6)(img)      # short side -> 6
        assert out2.shape == (6, 8)

    def test_crops_and_flips(self):
        img = np.arange(64, dtype=np.float32).reshape(8, 8)
        cc = transforms.CenterCrop(4)(img)
        np.testing.assert_array_equal(cc, img[2:6, 2:6])
        rc = transforms.RandomCrop(4)(img)
        assert rc.shape == (4, 4)
        fl = transforms.hflip(img)
        np.testing.assert_array_equal(fl, img[:, ::-1])


class TestDatasets:
    def test_fakedata_deterministic(self):
        ds = datasets.FakeData(num_samples=32, image_shape=(3, 8, 8),
                               num_classes=5, seed=1)
        x, y = ds[0]
        assert x.shape == (3, 8, 8) and 0 <= y < 5
        x2, _ = datasets.FakeData(num_samples=32, image_shape=(3, 8, 8),
                                  num_classes=5, seed=1)[0]
        np.testing.assert_array_equal(x, x2)

    def test_mnist_reads_idx(self, tmp_path):
        import gzip
        import struct
        imgs = np.random.RandomState(0).randint(
            0, 255, (4, 28, 28)).astype(np.uint8)
        labs = np.array([1, 2, 3, 4], np.uint8)
        ip = tmp_path / "imgs.gz"
        lp = tmp_path / "labs.gz"
        with gzip.open(ip, "wb") as f:
            f.write(struct.pack(">IIII", 2051, 4, 28, 28))
            f.write(imgs.tobytes())
        with gzip.open(lp, "wb") as f:
            f.write(struct.pack(">II", 2049, 4))
            f.write(labs.tobytes())
        ds = datasets.MNIST(image_path=str(ip), label_path=str(lp))
        assert len(ds) == 4
        x, y = ds[2]
        assert y == 3 and x.shape == (1, 28, 28)


class TestVisionOps:
    def test_nms_suppresses_overlaps(self):
        boxes = np.array([[0, 0, 10, 10],
                          [1, 1, 11, 11],       # IoU ~0.68 with box 0
                          [20, 20, 30, 30]], np.float32)
        scores = np.array([0.9, 0.8, 0.7], np.float32)
        keep = ops.nms(paddle.to_tensor(boxes), iou_threshold=0.5,
                       scores=paddle.to_tensor(scores)).numpy()
        np.testing.assert_array_equal(np.sort(keep), [0, 2])

    def test_nms_categories_dont_suppress(self):
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11]], np.float32)
        scores = np.array([0.9, 0.8], np.float32)
        cats = np.array([0, 1])
        keep = ops.nms(paddle.to_tensor(boxes), 0.5,
                       paddle.to_tensor(scores),
                       category_idxs=paddle.to_tensor(cats),
                       categories=[0, 1]).numpy()
        assert len(keep) == 2                  # different classes: both kept

    def test_roi_align_constant_field(self):
        """On a constant feature map every aligned ROI pools to that
        constant."""
        x = paddle.to_tensor(np.full((1, 2, 16, 16), 3.5, np.float32))
        boxes = paddle.to_tensor(np.array([[2, 2, 10, 10],
                                           [0, 0, 15, 15]], np.float32))
        out = ops.roi_align(x, boxes,
                            paddle.to_tensor(np.array([2], np.int32)),
                            output_size=4)
        assert list(out.shape) == [2, 2, 4, 4]
        np.testing.assert_allclose(out.numpy(), 3.5, rtol=1e-5)

    def test_roi_align_gradient_field(self):
        """Linear-in-x feature map: pooled value equals the ROI cell's
        center x coordinate (bilinear exactness on affine fields)."""
        H = W = 16
        ramp = np.tile(np.arange(W, dtype=np.float32), (H, 1))
        x = paddle.to_tensor(ramp[None, None])
        boxes = paddle.to_tensor(np.array([[4, 4, 12, 12]], np.float32))
        out = ops.roi_align(x, boxes,
                            paddle.to_tensor(np.array([1], np.int32)),
                            output_size=2, aligned=False).numpy()[0, 0]
        # cells span x in [4,8] and [8,12]; centers 6 and 10
        np.testing.assert_allclose(out[:, 0], 6.0, atol=0.26)
        np.testing.assert_allclose(out[:, 1], 10.0, atol=0.26)

    def test_box_iou(self):
        a = paddle.to_tensor(np.array([[0, 0, 10, 10]], np.float32))
        b = paddle.to_tensor(np.array([[5, 5, 15, 15],
                                       [20, 20, 30, 30]], np.float32))
        iou = ops.box_iou(a, b).numpy()
        np.testing.assert_allclose(iou[0, 0], 25 / 175, rtol=1e-5)
        assert iou[0, 1] == 0


class TestNewModelFamilies:
    """VGG / AlexNet (reference vision/models families); MobileNetV2 is in
    test_vision_mobile.py."""

    def test_vgg_variants(self):
        from paddle_tpu.vision.models import vgg11, vgg16
        x = paddle.to_tensor(np.zeros((1, 3, 224, 224), np.float32))
        assert list(vgg11(num_classes=4)(x).shape) == [1, 4]
        v = vgg16(batch_norm=True, num_classes=3)
        assert list(v(x).shape) == [1, 3]

    def test_alexnet(self):
        from paddle_tpu.vision.models import alexnet
        x = paddle.to_tensor(np.zeros((1, 3, 224, 224), np.float32))
        assert list(alexnet(num_classes=4)(x).shape) == [1, 4]


class TestDeeperFamilies:
    """ResNeXt / Wide ResNet and every variant factory (reference vision
    families). DenseNet, GoogLeNet and InceptionV3 are driven in
    test_vision_densenet_inception.py; MobileNetV1/V3, ShuffleNetV2 and
    SqueezeNet in test_vision_mobile.py."""

    def test_resnext_and_wide_resnet(self):
        from paddle_tpu.vision.models import (resnext50_32x4d,
                                              wide_resnet50_2)
        paddle.seed(0)
        drive(resnext50_32x4d(num_classes=5))
        paddle.seed(0)
        drive(wide_resnet50_2(num_classes=5))

    # reference __all__ parity: every factory constructs with a tiny head
    # and produces the right output shape forward-only
    @pytest.mark.parametrize("factory", [
        "densenet264", "shufflenet_v2_x0_25", "shufflenet_v2_x0_33",
        "shufflenet_v2_x1_5", "shufflenet_v2_x2_0", "shufflenet_v2_swish",
        "resnext50_64x4d", "resnext101_32x4d", "resnext152_32x4d",
        "resnext152_64x4d"])
    def test_variant_factories_construct(self, factory):
        paddle.seed(0)
        net = getattr(models, factory)(num_classes=3)
        net.eval()
        x = paddle.to_tensor(np.zeros((1, 3, 64, 64), np.float32))
        assert list(net(x).shape) == [1, 3], factory


class TestNewDatasets:
    def _png(self, arr, path):
        from PIL import Image
        Image.fromarray(arr).save(path)

    def test_cifar100_reads_fine_labels(self, tmp_path):
        import pickle, tarfile
        data = {b"data": np.random.RandomState(0).randint(
                    0, 255, (10, 3072), dtype=np.uint8).astype(np.uint8),
                b"fine_labels": list(range(10))}
        p = tmp_path / "cifar-100-python"
        p.mkdir()
        with open(p / "train", "wb") as f:
            pickle.dump(data, f)
        tar = tmp_path / "cifar-100-python.tar.gz"
        with tarfile.open(tar, "w:gz") as tf:
            tf.add(p / "train", arcname="cifar-100-python/train")
        from paddle_tpu.vision.datasets import Cifar100
        ds = Cifar100(data_file=str(tar), mode="train")
        assert len(ds) == 10
        img, label = ds[3]
        assert img.shape == (3, 32, 32) and int(label) == 3

    def test_flowers_split_quirk_and_read(self, tmp_path):
        import tarfile
        import scipy.io as scio
        jpg = tmp_path / "jpg"
        jpg.mkdir()
        for i in range(1, 5):
            self._png(np.full((8, 8, 3), i * 10, np.uint8),
                      jpg / f"image_{i:05d}.jpg")
        tar = tmp_path / "102flowers.tgz"
        with tarfile.open(tar, "w:gz") as tf:
            for i in range(1, 5):
                tf.add(jpg / f"image_{i:05d}.jpg",
                       arcname=f"jpg/image_{i:05d}.jpg")
        scio.savemat(tmp_path / "imagelabels.mat",
                     {"labels": np.array([[1, 1, 2, 2]])})
        # reference MODE_FLAG_MAP: train reads tstid
        scio.savemat(tmp_path / "setid.mat",
                     {"tstid": np.array([[1, 2, 3]]),
                      "trnid": np.array([[4]]),
                      "valid": np.array([[4]])})
        from paddle_tpu.vision.datasets import Flowers
        ds = Flowers(data_file=str(tar),
                     label_file=str(tmp_path / "imagelabels.mat"),
                     setid_file=str(tmp_path / "setid.mat"), mode="train")
        assert len(ds) == 3
        img, label = ds[0]
        assert img.shape[-1] == 3 and label.tolist() == [1]
        ds_test = Flowers(data_file=str(tar),
                          label_file=str(tmp_path / "imagelabels.mat"),
                          setid_file=str(tmp_path / "setid.mat"),
                          mode="test")
        assert len(ds_test) == 1

    def test_voc2012_pairs(self, tmp_path):
        import tarfile
        base = tmp_path / "VOCdevkit" / "VOC2012"
        (base / "ImageSets" / "Segmentation").mkdir(parents=True)
        (base / "JPEGImages").mkdir()
        (base / "SegmentationClass").mkdir()
        for n in ("a", "b"):
            self._png(np.zeros((6, 6, 3), np.uint8),
                      base / "JPEGImages" / f"{n}.jpg")
            self._png(np.ones((6, 6), np.uint8),
                      base / "SegmentationClass" / f"{n}.png")
        # reference MODE_FLAG_MAP: train->trainval, valid->val, test->train
        (base / "ImageSets" / "Segmentation" / "trainval.txt").write_text(
            "a\nb\n")
        (base / "ImageSets" / "Segmentation" / "val.txt").write_text("a\n")
        (base / "ImageSets" / "Segmentation" / "train.txt").write_text(
            "b\n")
        tar = tmp_path / "voc.tar"
        with tarfile.open(tar, "w") as tf:
            tf.add(tmp_path / "VOCdevkit", arcname="VOCdevkit")
        from paddle_tpu.vision.datasets import VOC2012
        ds = VOC2012(data_file=str(tar), mode="train")
        assert len(ds) == 2                       # trainval split
        assert len(VOC2012(data_file=str(tar), mode="valid")) == 1
        assert len(VOC2012(data_file=str(tar), mode="test")) == 1
        img, mask = ds[0]
        assert img.shape == (6, 6, 3) and mask.shape == (6, 6)
        assert (mask == 1).all()

    def test_dataset_folder_and_image_folder(self, tmp_path):
        root = tmp_path / "ds"
        for cls in ("cat", "dog"):
            (root / cls).mkdir(parents=True)
            for i in range(2):
                self._png(np.full((4, 4, 3), i, np.uint8),
                          root / cls / f"{i}.png")
        from paddle_tpu.vision.datasets import DatasetFolder, ImageFolder
        ds = DatasetFolder(str(root))
        assert ds.classes == ["cat", "dog"]
        assert len(ds) == 4
        sample, target = ds[0]
        assert sample.shape == (4, 4, 3) and target == 0
        flat = ImageFolder(str(root))
        assert len(flat) == 4
        [only] = flat[0]
        assert only.shape == (4, 4, 3)

    def test_tar_datasets_survive_forked_workers(self, tmp_path):
        """Flowers/VOC keep a lazy per-process tar handle; forked
        DataLoader workers must re-open rather than share the parent fd."""
        import tarfile
        import scipy.io as scio
        from paddle_tpu.io import DataLoader
        jpg = tmp_path / "jpg"
        jpg.mkdir()
        for i in range(1, 9):
            self._png(np.full((8, 8, 3), i * 7 % 255, np.uint8),
                      jpg / f"image_{i:05d}.jpg")
        tar = tmp_path / "102flowers.tgz"
        with tarfile.open(tar, "w:gz") as tf:
            for i in range(1, 9):
                tf.add(jpg / f"image_{i:05d}.jpg",
                       arcname=f"jpg/image_{i:05d}.jpg")
        scio.savemat(tmp_path / "imagelabels.mat",
                     {"labels": np.arange(1, 9)[None]})
        scio.savemat(tmp_path / "setid.mat",
                     {"tstid": np.arange(1, 9)[None],
                      "trnid": np.array([[1]]),
                      "valid": np.array([[1]])})
        from paddle_tpu.vision.datasets import Flowers
        ds = Flowers(data_file=str(tar),
                     label_file=str(tmp_path / "imagelabels.mat"),
                     setid_file=str(tmp_path / "setid.mat"), mode="train")
        loader = DataLoader(ds, batch_size=2, num_workers=2)
        seen = 0
        for img, label in loader:
            assert tuple(img.shape[1:]) == (8, 8, 3)
            seen += int(img.shape[0])
        assert seen == 8
