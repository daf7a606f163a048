"""ResNet.

Parity: ``models/resnet/ResNet.scala:59-266`` — basicBlock/bottleneck,
shortcutType A (zero-padded identity) / B (1x1 conv projection) / C, CIFAR-10
depth-6n+2 variant and ImageNet depth-{18,34,50,101,152} variants.

The reference's ``optnet`` buffer sharing (``ResNet.scala:34-45``,
SpatialShareConvolution + shared gradInput storages) is moot under XLA's
allocator — documented divergence (SURVEY.md section 7 build order #8).
"""

from __future__ import annotations

import bigdl_tpu.nn as nn


def _conv(n_in, n_out, kw, kh, sw=1, sh=1, pw=0, ph=0):
    """Conv WITHOUT bias: every conv here feeds a BatchNorm, which
    subtracts the per-channel mean — ANY constant conv bias is cancelled
    exactly in the training forward and receives an identically-zero
    gradient (it only shifts the mean BN removes).  Training dynamics are
    therefore identical to the biased form, and the parameter is dead
    weight whose dy-reduction cost XLA still paid every step (measured
    ~17% of the ResNet-50 backward).  The reference zero-initialises
    these biases too (``ResNet.scala:113``).  Note: snapshots saved by
    the OLD biased builders are not loadable into this structure —
    ``load_model_snapshot`` raises a structure error rather than
    silently mis-assigning."""
    return nn.SpatialConvolution(n_in, n_out, kw, kh, sw, sh, pw, ph,
                                 with_bias=False)


def _shortcut(n_in: int, n_out: int, stride: int,
              shortcut_type: str) -> nn.Module:
    use_conv = shortcut_type == "C" or \
        (shortcut_type == "B" and n_in != n_out)
    if use_conv:
        return (nn.Sequential()
                .add(_conv(n_in, n_out, 1, 1, stride, stride))
                .add(nn.SpatialBatchNormalization(n_out)))
    if n_in != n_out:  # type A: stride then zero-pad channels
        return (nn.Sequential()
                .add(nn.SpatialAveragePooling(1, 1, stride, stride))
                .add(nn.Padding(1, n_out - n_in, 3)))
    if stride != 1:
        return nn.SpatialAveragePooling(1, 1, stride, stride)
    return nn.Identity()


def basic_block(n_in: int, n: int, stride: int,
                shortcut_type: str = "B") -> nn.Sequential:
    s = (nn.Sequential()
         .add(_conv(n_in, n, 3, 3, stride, stride, 1, 1))
         .add(nn.SpatialBatchNormalization(n))
         .add(nn.ReLU(True))
         .add(_conv(n, n, 3, 3, 1, 1, 1, 1))
         .add(nn.SpatialBatchNormalization(n)))
    return (nn.Sequential()
            .add(nn.ConcatTable()
                 .add(s)
                 .add(_shortcut(n_in, n, stride, shortcut_type)))
            .add(nn.CAddTable(True))
            .add(nn.ReLU(True)))


def bottleneck(n_in: int, n: int, stride: int,
               shortcut_type: str = "B") -> nn.Sequential:
    out = n * 4
    s = (nn.Sequential()
         .add(_conv(n_in, n, 1, 1, 1, 1))
         .add(nn.SpatialBatchNormalization(n))
         .add(nn.ReLU(True))
         .add(_conv(n, n, 3, 3, stride, stride, 1, 1))
         .add(nn.SpatialBatchNormalization(n))
         .add(nn.ReLU(True))
         .add(_conv(n, out, 1, 1, 1, 1))
         .add(nn.SpatialBatchNormalization(out)))
    return (nn.Sequential()
            .add(nn.ConcatTable()
                 .add(s)
                 .add(_shortcut(n_in, out, stride, shortcut_type)))
            .add(nn.CAddTable(True))
            .add(nn.ReLU(True)))


_IMAGENET_CFG = {
    18: ([2, 2, 2, 2], 512, basic_block),
    34: ([3, 4, 6, 3], 512, basic_block),
    50: ([3, 4, 6, 3], 2048, bottleneck),
    101: ([3, 4, 23, 3], 2048, bottleneck),
    152: ([3, 8, 36, 3], 2048, bottleneck),
}


def ResNet(class_num: int = 1000, depth: int = 50,
           shortcut_type: str = "B",
           dataset: str = "imagenet") -> nn.Sequential:
    model = nn.Sequential()

    if dataset == "imagenet":
        cfg, n_features, block = _IMAGENET_CFG[depth]

        def layer(block_fn, n_in, n, count, stride):
            seq = nn.Sequential()
            for i in range(count):
                seq.add(block_fn(n_in if i == 0 else
                                 (n * 4 if block_fn is bottleneck else n),
                                 n, stride if i == 0 else 1, shortcut_type))
            return seq

        model.add(_conv(3, 64, 7, 7, 2, 2, 3, 3))
        model.add(nn.SpatialBatchNormalization(64))
        model.add(nn.ReLU(True))
        model.add(nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1))
        widths = [64, 128, 256, 512]
        n_in = 64
        for i, (w, c) in enumerate(zip(widths, cfg)):
            model.add(layer(block, n_in, w, c, 1 if i == 0 else 2))
            n_in = w * 4 if block is bottleneck else w
        model.add(nn.SpatialAveragePooling(7, 7, 1, 1))
        model.add(nn.View(n_features).set_num_input_dims(3))
        model.add(nn.Linear(n_features, class_num))
        model.add(nn.LogSoftMax())
    elif dataset == "cifar10":
        assert (depth - 2) % 6 == 0, "cifar depth must be 6n+2"
        n = (depth - 2) // 6

        def layer(n_in, width, count, stride):
            seq = nn.Sequential()
            for i in range(count):
                seq.add(basic_block(n_in if i == 0 else width, width,
                                    stride if i == 0 else 1, shortcut_type))
            return seq

        model.add(_conv(3, 16, 3, 3, 1, 1, 1, 1))
        model.add(nn.SpatialBatchNormalization(16))
        model.add(nn.ReLU(True))
        model.add(layer(16, 16, n, 1))
        model.add(layer(16, 32, n, 2))
        model.add(layer(32, 64, n, 2))
        model.add(nn.SpatialAveragePooling(8, 8, 1, 1))
        model.add(nn.View(64).set_num_input_dims(3))
        model.add(nn.Linear(64, class_num))
        model.add(nn.LogSoftMax())
    else:
        raise ValueError(f"unknown dataset {dataset}")
    return model


def cifar10_decay(epoch: int) -> float:
    """LR decay exponent schedule (``models/resnet/Train.scala:38-39``)."""
    return 2.0 if epoch >= 122 else (1.0 if epoch >= 81 else 0.0)


def train_main(argv=None):
    """CLI train entry (``models/resnet/Train.scala:41-118``): ResNet-20-ish
    on CIFAR-10 with pad-4 random crop + flip, EpochDecay LR, nesterov SGD."""
    import argparse

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.image import (BGRImgCropper, BGRImgNormalizer,
                                         BGRImgToBatch, BytesToBGRImg, HFlip)
    from bigdl_tpu.dataset.loaders import load_cifar10
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.dataset.loaders import (CIFAR10_TEST_MEAN,
                                           CIFAR10_TEST_STD,
                                           CIFAR10_TRAIN_MEAN,
                                           CIFAR10_TRAIN_STD)
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim import (EpochDecay, Optimizer, SGD, Top1Accuracy,
                                 Trigger)
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging

    p = argparse.ArgumentParser("resnet-train")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("-b", "--batchSize", type=int, default=128)
    p.add_argument("--nepochs", type=int, default=165)
    p.add_argument("--depth", type=int, default=20)
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--shortcutType", default="A")
    p.add_argument("-r", "--learningRate", type=float, default=0.1)
    p.add_argument("--weightDecay", type=float, default=1e-4)
    p.add_argument("-m", "--momentum", type=float, default=0.9)
    p.add_argument("--dampening", type=float, default=0.0)
    p.add_argument("--nesterov", action=argparse.BooleanOptionalAction,
                   default=True)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--model", default=None)
    p.add_argument("--state", default=None, help="state snapshot to resume")
    args = p.parse_args(argv)

    init_logging()
    enable_compile_cache()
    Engine.init()
    train_set = DataSet.array(load_cifar10(args.folder, train=True)) >> \
        BytesToBGRImg() >> BGRImgNormalizer(CIFAR10_TRAIN_MEAN, CIFAR10_TRAIN_STD) >> \
        HFlip(0.5) >> BGRImgCropper(32, 32, padding=4) >> \
        BGRImgToBatch(args.batchSize)
    val_set = DataSet.array(load_cifar10(args.folder, train=False)) >> \
        BytesToBGRImg() >> BGRImgNormalizer(CIFAR10_TEST_MEAN, CIFAR10_TEST_STD) >> \
        BGRImgToBatch(args.batchSize)

    model = ResNet(class_num=args.classes, depth=args.depth,
                   shortcut_type=args.shortcutType, dataset="cifar10")
    if args.model:
        from bigdl_tpu.utils.file import load_model_snapshot
        load_model_snapshot(model, args.model)

    optimizer = Optimizer(model=model, dataset=train_set,
                          criterion=CrossEntropyCriterion())
    optimizer.set_optim_method(SGD(
        learning_rate=args.learningRate, weight_decay=args.weightDecay,
        momentum=args.momentum, dampening=args.dampening,
        nesterov=args.nesterov,
        learning_rate_schedule=EpochDecay(cifar10_decay)))
    if args.state:
        from bigdl_tpu.utils.file import File
        optimizer.set_state(File.load(args.state))
    optimizer.set_end_when(Trigger.max_epoch(args.nepochs))
    optimizer.set_validation(Trigger.every_epoch(), val_set,
                             [Top1Accuracy()])
    if args.checkpoint:
        optimizer.set_checkpoint(args.checkpoint, Trigger.every_epoch())
    return optimizer.optimize()


def test_main(argv=None):
    """CLI eval entry (``models/resnet/Test.scala``)."""
    import argparse

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.image import (BGRImgNormalizer, BGRImgToBatch,
                                         BytesToBGRImg)
    from bigdl_tpu.dataset.loaders import load_cifar10
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.dataset.loaders import (CIFAR10_TEST_MEAN,
                                           CIFAR10_TEST_STD)
    from bigdl_tpu.optim import LocalValidator, Top1Accuracy
    from bigdl_tpu.utils.file import load_model_snapshot
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging

    p = argparse.ArgumentParser("resnet-test")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("--model", required=True)
    p.add_argument("-b", "--batchSize", type=int, default=128)
    p.add_argument("--depth", type=int, default=20)
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--shortcutType", default="A")
    args = p.parse_args(argv)

    init_logging()
    enable_compile_cache()
    Engine.init()
    val_set = DataSet.array(load_cifar10(args.folder, train=False)) >> \
        BytesToBGRImg() >> BGRImgNormalizer(CIFAR10_TEST_MEAN, CIFAR10_TEST_STD) >> \
        BGRImgToBatch(args.batchSize)
    model = ResNet(class_num=args.classes, depth=args.depth,
                   shortcut_type=args.shortcutType, dataset="cifar10")
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
