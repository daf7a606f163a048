"""VGG models.

Parity: ``models/vgg/VggForCifar10.scala`` (conv+BN stacks for 32x32),
``models/vgg/Vgg_16.scala``, ``models/vgg/Vgg_19.scala`` (ImageNet).
"""

from __future__ import annotations

import bigdl_tpu.nn as nn


def VggForCifar10(class_num: int = 10) -> nn.Sequential:
    model = nn.Sequential()

    def conv_bn_relu(ni, no):
        model.add(nn.SpatialConvolution(ni, no, 3, 3, 1, 1, 1, 1))
        model.add(nn.SpatialBatchNormalization(no, 1e-3))
        model.add(nn.ReLU(True))

    conv_bn_relu(3, 64)
    model.add(nn.Dropout(0.3))
    conv_bn_relu(64, 64)
    model.add(nn.SpatialMaxPooling(2, 2, 2, 2).ceil())
    conv_bn_relu(64, 128)
    model.add(nn.Dropout(0.4))
    conv_bn_relu(128, 128)
    model.add(nn.SpatialMaxPooling(2, 2, 2, 2).ceil())
    conv_bn_relu(128, 256)
    model.add(nn.Dropout(0.4))
    conv_bn_relu(256, 256)
    model.add(nn.Dropout(0.4))
    conv_bn_relu(256, 256)
    model.add(nn.SpatialMaxPooling(2, 2, 2, 2).ceil())
    conv_bn_relu(256, 512)
    model.add(nn.Dropout(0.4))
    conv_bn_relu(512, 512)
    model.add(nn.Dropout(0.4))
    conv_bn_relu(512, 512)
    model.add(nn.SpatialMaxPooling(2, 2, 2, 2).ceil())
    conv_bn_relu(512, 512)
    model.add(nn.Dropout(0.4))
    conv_bn_relu(512, 512)
    model.add(nn.Dropout(0.4))
    conv_bn_relu(512, 512)
    model.add(nn.SpatialMaxPooling(2, 2, 2, 2).ceil())
    model.add(nn.View(512))
    model.add(nn.Dropout(0.5))
    model.add(nn.Linear(512, 512))
    model.add(nn.BatchNormalization(512))
    model.add(nn.ReLU(True))
    model.add(nn.Dropout(0.5))
    model.add(nn.Linear(512, class_num))
    model.add(nn.LogSoftMax())
    return model


def _vgg_imagenet(cfg, class_num: int) -> nn.Sequential:
    model = nn.Sequential()
    in_c = 3
    for v in cfg:
        if v == "M":
            model.add(nn.SpatialMaxPooling(2, 2, 2, 2))
        else:
            model.add(nn.SpatialConvolution(in_c, v, 3, 3, 1, 1, 1, 1))
            model.add(nn.ReLU(True))
            in_c = v
    model.add(nn.View(512 * 7 * 7))
    model.add(nn.Linear(512 * 7 * 7, 4096))
    model.add(nn.Threshold(0, 1e-6))
    model.add(nn.Dropout(0.5))
    model.add(nn.Linear(4096, 4096))
    model.add(nn.Threshold(0, 1e-6))
    model.add(nn.Dropout(0.5))
    model.add(nn.Linear(4096, class_num))
    model.add(nn.LogSoftMax())
    return model


def Vgg_16(class_num: int = 1000) -> nn.Sequential:
    return _vgg_imagenet(
        [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
         512, 512, 512, "M", 512, 512, 512, "M"], class_num)


def Vgg_19(class_num: int = 1000) -> nn.Sequential:
    return _vgg_imagenet(
        [64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M",
         512, 512, 512, 512, "M", 512, 512, 512, 512, "M"], class_num)


def _cifar_set(folder: str, batch_size: int, train: bool):
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.image import (BGRImgNormalizer, BGRImgToBatch,
                                         BytesToBGRImg)
    from bigdl_tpu.dataset.loaders import (CIFAR10_TEST_MEAN,
                                           CIFAR10_TEST_STD,
                                           CIFAR10_TRAIN_MEAN,
                                           CIFAR10_TRAIN_STD, load_cifar10)
    mean = CIFAR10_TRAIN_MEAN if train else CIFAR10_TEST_MEAN
    std = CIFAR10_TRAIN_STD if train else CIFAR10_TEST_STD
    return DataSet.array(load_cifar10(folder, train=train)) >> \
        BytesToBGRImg() >> BGRImgNormalizer(mean, std) >> \
        BGRImgToBatch(batch_size)


def train_main(argv=None):
    """CLI train entry (``models/vgg/Train.scala:38-97``): VggForCifar10 on
    CIFAR-10, SGD lr 0.01 / wd 5e-4 / momentum 0.9 with EpochStep(25, 0.5)."""
    import argparse

    from bigdl_tpu.engine import Engine
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import (EpochStep, Optimizer, SGD, Top1Accuracy,
                                 Trigger)
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging

    p = argparse.ArgumentParser("vgg-train")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("-b", "--batchSize", type=int, default=112)
    p.add_argument("-e", "--maxEpoch", type=int, default=90)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--overWrite", action="store_true")
    p.add_argument("--model", default=None)
    p.add_argument("--state", default=None, help="state snapshot to resume")
    args = p.parse_args(argv)

    init_logging()
    enable_compile_cache()
    Engine.init()
    train_set = _cifar_set(args.folder, args.batchSize, train=True)
    val_set = _cifar_set(args.folder, args.batchSize, train=False)

    model = VggForCifar10(10)
    if args.model:
        from bigdl_tpu.utils.file import load_model_snapshot
        load_model_snapshot(model, args.model)

    optimizer = Optimizer(model=model, dataset=train_set,
                          criterion=ClassNLLCriterion())
    optimizer.set_optim_method(SGD(
        learning_rate=0.01, weight_decay=0.0005, momentum=0.9,
        dampening=0.0, learning_rate_schedule=EpochStep(25, 0.5)))
    if args.state:
        from bigdl_tpu.utils.file import File
        optimizer.set_state(File.load(args.state))
    optimizer.set_end_when(Trigger.max_epoch(args.maxEpoch))
    optimizer.set_validation(Trigger.every_epoch(), val_set,
                             [Top1Accuracy()])
    if args.checkpoint:
        optimizer.set_checkpoint(args.checkpoint, Trigger.every_epoch())
    if args.overWrite:
        optimizer.overwrite_checkpoint_()
    return optimizer.optimize()


def test_main(argv=None):
    """CLI eval entry (``models/vgg/Test.scala``): Top-1 on CIFAR-10 val."""
    import argparse

    from bigdl_tpu.engine import Engine
    from bigdl_tpu.optim import LocalValidator, Top1Accuracy
    from bigdl_tpu.utils.file import load_model_snapshot
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging

    p = argparse.ArgumentParser("vgg-test")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("--model", required=True)
    p.add_argument("-b", "--batchSize", type=int, default=112)
    args = p.parse_args(argv)

    init_logging()
    enable_compile_cache()
    Engine.init()
    val_set = _cifar_set(args.folder, args.batchSize, train=False)
    model = VggForCifar10(10)
    load_model_snapshot(model, args.model)
    results = LocalValidator(model, val_set).test([Top1Accuracy()])
    for r in results:
        print(r)
    return results


if __name__ == "__main__":
    import sys
    if len(sys.argv) > 1 and sys.argv[1] == "test":
        test_main(sys.argv[2:])
    else:
        train_main()
