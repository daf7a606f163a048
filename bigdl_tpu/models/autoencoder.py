"""MNIST autoencoder (``models/autoencoder/Autoencoder.scala``): 784 ->
classNum hidden -> 784 sigmoid, trained with MSE reconstruction."""

import bigdl_tpu.nn as nn


def Autoencoder(class_num: int = 32) -> nn.Sequential:
    return (nn.Sequential()
            .add(nn.Reshape([28 * 28]))
            .add(nn.Linear(28 * 28, class_num))
            .add(nn.ReLU(True))
            .add(nn.Linear(class_num, 28 * 28))
            .add(nn.Sigmoid()))


def train_main(argv=None):
    """CLI train entry (``models/autoencoder/Train.scala``): MSE
    reconstruction of MNIST digits, SGD lr 0.01 / momentum 0.9."""
    import argparse

    import numpy as np

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.image import (BytesToGreyImg, GreyImgNormalizer,
                                         GreyImgToBatch)
    from bigdl_tpu.dataset.loaders import load_mnist
    from bigdl_tpu.dataset.transformer import Lambda, MiniBatch
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.nn import MSECriterion
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging

    p = argparse.ArgumentParser("autoencoder-train")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("-b", "--batchSize", type=int, default=150)
    p.add_argument("-e", "--maxEpoch", type=int, default=10)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--model", default=None, help="model snapshot to resume")
    p.add_argument("--state", default=None, help="state snapshot to resume")
    args = p.parse_args(argv)

    init_logging()
    enable_compile_cache()
    Engine.init()
    train = load_mnist(f"{args.folder}/train-images-idx3-ubyte",
                       f"{args.folder}/train-labels-idx1-ubyte")

    def to_reconstruction(b):
        # target == flattened input (``Train.scala``'s toAutoencoderBatch)
        flat = np.asarray(b.data).reshape(b.data.shape[0], -1)
        return MiniBatch(flat, flat)

    train_set = DataSet.array(train) >> BytesToGreyImg(28, 28) >> \
        GreyImgNormalizer(0.13066047740239506, 0.3081078) >> \
        GreyImgToBatch(args.batchSize) >> Lambda(to_reconstruction)

    model = Autoencoder(32)
    if args.model:
        from bigdl_tpu.utils.file import load_model_snapshot
        load_model_snapshot(model, args.model)
    optimizer = Optimizer(model=model, dataset=train_set,
                          criterion=MSECriterion())
    optimizer.set_optim_method(SGD(learning_rate=0.01, momentum=0.9))
    if args.state:
        from bigdl_tpu.utils.file import File
        optimizer.set_state(File.load(args.state))
    optimizer.set_end_when(Trigger.max_epoch(args.maxEpoch))
    if args.checkpoint:
        optimizer.set_checkpoint(args.checkpoint, Trigger.every_epoch())
    return optimizer.optimize()


if __name__ == "__main__":
    train_main()
