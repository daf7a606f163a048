"""LeNet-5 (``models/lenet/LeNet5.scala:25-40``) and its train/test entry
points (``models/lenet/Train.scala:41-104``, ``Test.scala``).

The Sequential graph matches the reference layer-for-layer: conv(1->6,5x5)
-> tanh -> maxpool -> tanh -> conv(6->12,5x5) -> maxpool -> reshape ->
linear(100) -> tanh -> linear(classNum) -> logsoftmax.
"""

from __future__ import annotations

import bigdl_tpu.nn as nn


def LeNet5(class_num: int = 10) -> nn.Sequential:
    return (nn.Sequential()
            .add(nn.Reshape([1, 28, 28]))
            .add(nn.SpatialConvolution(1, 6, 5, 5))
            .add(nn.Tanh())
            .add(nn.SpatialMaxPooling(2, 2, 2, 2))
            .add(nn.Tanh())
            .add(nn.SpatialConvolution(6, 12, 5, 5))
            .add(nn.SpatialMaxPooling(2, 2, 2, 2))
            .add(nn.Reshape([12 * 4 * 4]))
            .add(nn.Linear(12 * 4 * 4, 100))
            .add(nn.Tanh())
            .add(nn.Linear(100, class_num))
            .add(nn.LogSoftMax()))


def train_main(argv=None):
    """CLI train entry (scopt-flag parity with ``models/lenet/Train.scala``:
    -f data folder, -b batch size, -e max epoch, -r learning rate...)."""
    import argparse

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.image import (BytesToGreyImg, GreyImgNormalizer,
                                         GreyImgToBatch)
    from bigdl_tpu.dataset.loaders import load_mnist
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import (Optimizer, SGD, Top1Accuracy, Trigger)

    p = argparse.ArgumentParser("lenet-train")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("-b", "--batchSize", type=int, default=128)
    p.add_argument("-e", "--maxEpoch", type=int, default=10)
    p.add_argument("-r", "--learningRate", type=float, default=0.05)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--model", default=None, help="model snapshot to resume")
    p.add_argument("--state", default=None, help="state snapshot to resume")
    args = p.parse_args(argv)

    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging
    init_logging()
    enable_compile_cache()
    Engine.init()
    train_mean, train_std = 0.13066047740239506, 0.3081078

    train = load_mnist(f"{args.folder}/train-images-idx3-ubyte",
                       f"{args.folder}/train-labels-idx1-ubyte")
    val = load_mnist(f"{args.folder}/t10k-images-idx3-ubyte",
                     f"{args.folder}/t10k-labels-idx1-ubyte")

    train_set = DataSet.array(train) >> BytesToGreyImg(28, 28) >> \
        GreyImgNormalizer(train_mean, train_std) >> \
        GreyImgToBatch(args.batchSize)
    val_set = DataSet.array(val) >> BytesToGreyImg(28, 28) >> \
        GreyImgNormalizer(train_mean, train_std) >> \
        GreyImgToBatch(args.batchSize)

    model = LeNet5(10)
    if args.model:
        from bigdl_tpu.utils.file import load_model_snapshot
        load_model_snapshot(model, args.model)

    optimizer = Optimizer(model=model, dataset=train_set,
                          criterion=ClassNLLCriterion())
    optimizer.set_optim_method(SGD(learning_rate=args.learningRate))
    if args.state:
        from bigdl_tpu.utils.file import File
        optimizer.set_state(File.load(args.state))
    optimizer.set_end_when(Trigger.max_epoch(args.maxEpoch))
    optimizer.set_validation(Trigger.every_epoch(), val_set,
                             [Top1Accuracy()])
    if args.checkpoint:
        optimizer.set_checkpoint(args.checkpoint, Trigger.every_epoch())
    return optimizer.optimize()





def test_main(argv=None):
    """CLI eval entry (``models/lenet/Test.scala``): Top-1 on MNIST t10k."""
    import argparse

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.image import (BytesToGreyImg, GreyImgNormalizer,
                                         GreyImgToBatch)
    from bigdl_tpu.dataset.loaders import load_mnist
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.optim import LocalValidator, Top1Accuracy
    from bigdl_tpu.utils.file import load_model_snapshot
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging

    p = argparse.ArgumentParser("lenet-test")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("--model", required=True)
    p.add_argument("-b", "--batchSize", type=int, default=128)
    args = p.parse_args(argv)

    init_logging()
    enable_compile_cache()
    Engine.init()
    val = load_mnist(f"{args.folder}/t10k-images-idx3-ubyte",
                     f"{args.folder}/t10k-labels-idx1-ubyte")
    val_set = DataSet.array(val) >> BytesToGreyImg(28, 28) >> \
        GreyImgNormalizer(0.13251460584233699, 0.31048024) >> \
        GreyImgToBatch(args.batchSize)
    model = LeNet5(10)
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
