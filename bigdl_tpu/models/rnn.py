"""SimpleRNN character/word language model.

Parity: ``models/rnn/SimpleRNN.scala:31-33`` — LookupTable-free one-hot
input -> Recurrent(RnnCell) -> TimeDistributed(Linear) -> LogSoftMax, with
truncated BPTT; plus LSTM/GRU variants (BASELINE.json config 5 names
"nn.LSTM" — provided as an idiomatic extension, the reference vintage has
only RnnCell).
"""

from __future__ import annotations

import bigdl_tpu.nn as nn


def SimpleRNN(input_size: int = 100, hidden_size: int = 40,
              output_size: int = 100, bptt: int = 4,
              cell: str = "rnn") -> nn.Sequential:
    cells = {"rnn": lambda: nn.RnnCell(input_size, hidden_size, "tanh"),
             "lstm": lambda: nn.LSTMCell(input_size, hidden_size),
             "gru": lambda: nn.GRUCell(input_size, hidden_size)}
    return (nn.Sequential()
            .add(nn.Recurrent(hidden_size, bptt_truncate=bptt)
                 .add(cells[cell]()))
            .add(nn.TimeDistributed(nn.Linear(hidden_size, output_size)))
            .add(nn.TimeDistributed(nn.LogSoftMax())))


def TextClassifierRNN(vocab_size: int, embed_dim: int = 128,
                      hidden_size: int = 128, class_num: int = 20,
                      cell: str = "lstm") -> nn.Sequential:
    """LSTM text classifier (BASELINE config 5): embed -> recurrent ->
    last-step hidden -> linear -> logsoftmax."""
    cells = {"rnn": lambda: nn.RnnCell(embed_dim, hidden_size, "tanh"),
             "lstm": lambda: nn.LSTMCell(embed_dim, hidden_size),
             "gru": lambda: nn.GRUCell(embed_dim, hidden_size)}
    return (nn.Sequential()
            .add(nn.LookupTable(vocab_size, embed_dim))
            .add(nn.Recurrent(hidden_size).add(cells[cell]()))
            .add(nn.Select(2, -1))       # last time step (B, T, H) -> (B, H)
            .add(nn.Linear(hidden_size, class_num))
            .add(nn.LogSoftMax()))


def train_main(argv=None):
    """CLI train entry (``models/rnn/Train.scala:35-105`` flag parity):
    tokenizes ``<folder>/input.txt``, trains SimpleRNN on next-token
    prediction with per-epoch loss validation and checkpointing."""
    import argparse

    import numpy as np

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.text import (LabeledSentenceToSample,
                                        WordTokenizer, load_in_data)
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.nn import ClassNLLCriterion, TimeDistributedCriterion
    from bigdl_tpu.optim import Loss, Optimizer, SGD, Trigger
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging

    p = argparse.ArgumentParser("rnn-train")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("--model", default=None, help="model snapshot location")
    p.add_argument("--state", default=None, help="state snapshot location")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("-r", "--learningRate", type=float, default=0.1)
    p.add_argument("-m", "--momentum", type=float, default=0.0)
    p.add_argument("--weightDecay", type=float, default=0.0)
    p.add_argument("--dampening", type=float, default=0.0)
    p.add_argument("-h2", "--hidden", type=int, default=40)
    p.add_argument("--vocab", type=int, default=4000)
    p.add_argument("--bptt", type=int, default=4)
    p.add_argument("-e", "--nEpochs", type=int, default=30)
    p.add_argument("-b", "--batchSize", type=int, default=8)
    args = p.parse_args(argv)

    init_logging()
    enable_compile_cache()
    Engine.init()
    dictionary_length = args.vocab + 1
    WordTokenizer(f"{args.folder}/input.txt", args.folder,
                  dictionary_length=dictionary_length).process()
    train, val, train_max, val_max = load_in_data(
        args.folder, dictionary_length)

    train_set = DataSet.array(train) >> \
        LabeledSentenceToSample(dictionary_length,
                                fix_data_length=train_max,
                                fix_label_length=train_max) >> \
        SampleToBatch(args.batchSize, drop_last=True)
    val_set = DataSet.array(val) >> \
        LabeledSentenceToSample(dictionary_length,
                                fix_data_length=val_max,
                                fix_label_length=val_max) >> \
        SampleToBatch(args.batchSize, drop_last=True)

    model = SimpleRNN(input_size=dictionary_length,
                      hidden_size=args.hidden,
                      output_size=dictionary_length, bptt=args.bptt)
    if args.model:
        from bigdl_tpu.utils.file import load_model_snapshot
        load_model_snapshot(model, args.model)

    criterion = TimeDistributedCriterion(ClassNLLCriterion(),
                                         size_average=True)
    optimizer = Optimizer(model=model, dataset=train_set,
                          criterion=criterion)
    optimizer.set_optim_method(SGD(
        learning_rate=args.learningRate, momentum=args.momentum,
        weight_decay=args.weightDecay, dampening=args.dampening))
    optimizer.set_end_when(Trigger.max_epoch(args.nEpochs))
    optimizer.set_validation(Trigger.every_epoch(), val_set,
                             [Loss(criterion)])
    if args.checkpoint:
        optimizer.set_checkpoint(args.checkpoint, Trigger.every_epoch())
    if args.state:
        from bigdl_tpu.utils.file import File
        optimizer.set_state(File.load(args.state))
    return optimizer.optimize()


def test_main(argv=None):
    """CLI generation entry (``models/rnn/Test.scala:39-92``): extends each
    ``test.txt`` sentence by ``--words`` sampled tokens."""
    import argparse

    import jax
    import numpy as np

    from bigdl_tpu.dataset.text import Dictionary, read_sentence
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.utils.file import load_model_snapshot
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging
    from bigdl_tpu.utils.random_generator import RNG

    p = argparse.ArgumentParser("rnn-test")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("--model", required=True)
    p.add_argument("--words", type=int, required=True)
    p.add_argument("-h2", "--hidden", type=int, default=40)
    p.add_argument("--vocab", type=int, default=4000)
    args = p.parse_args(argv)

    init_logging()
    enable_compile_cache()
    Engine.init()
    vocab = Dictionary(args.folder)
    dictionary_length = args.vocab + 1

    model = SimpleRNN(input_size=dictionary_length, hidden_size=args.hidden,
                      output_size=dictionary_length)
    load_model_snapshot(model, args.model)
    model.evaluate()

    sentences = [[float(vocab.get_index(t)) for t in line]
                 for line in read_sentence(args.folder)]
    rng = RNG()
    for _ in range(args.words):
        grown = []
        for seq in sentences:
            onehot = np.zeros((1, len(seq), dictionary_length), np.float32)
            onehot[0, np.arange(len(seq)), np.asarray(seq, np.int64)] = 1.0
            out = np.asarray(model.forward(onehot))[0, -1]
            probs = np.exp(out - out.max())
            probs /= probs.sum()
            cum = np.cumsum(probs)
            nxt = int(np.searchsorted(cum, rng.uniform(0.0, 1.0)))
            grown.append(seq + [float(min(nxt, dictionary_length - 1))])
        sentences = grown

    results = [" ".join(vocab.get_word(t) for t in seq)
               for seq in sentences]
    for line in results:
        print(line)
    return results


if __name__ == "__main__":
    import sys
    if len(sys.argv) > 1 and sys.argv[1] == "test":
        test_main(sys.argv[2:])
    else:
        train_main()
