"""The one general traffic generator.  A traffic mix is a data file under
``benchmark/traffic/`` (see README.md for the keys); this module turns a
file and a seed into requests, due times and budgets.

The work does not depend on ``--seed``: a file holds a fixed multiset of
prompt lengths and of output lengths, the request sequence is made of
consecutive blocks that each hold one copy of the multiset, permuted by
the FILE's own ``order_seed``; ``--seed`` draws the token ids (and the
weights) and nothing else.  Which lengths share the slots of a closed loop
at a time, and which prompts queue behind which in an open one, IS the
work: PR 23's first chip runs of a closed loop whose order followed
``--seed`` differed by 3.6% between two seeds on a device that was 98.6%
busy.  Two seeds offer the same lengths in the same order at the same
pace, with other tokens."""

from __future__ import annotations

import json
import os
from typing import Iterator, List, Tuple

import numpy as np

KINDS = ("train", "closed", "open")


def load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        spec = json.load(f)
    if spec.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}, "
                         f"got {spec.get('kind')!r}")
    return spec


def path_for(root: str, traffic: str) -> str:
    return os.path.join(root, "benchmark", "traffic", traffic + ".json")


def expand(multiset) -> List[int]:
    """``[[value, count], ...]`` -> the flat multiset, in file order."""
    out: List[int] = []
    for value, count in multiset:
        out.extend([int(value)] * int(count))
    return out


def block_size(spec: dict) -> int:
    return len(expand(spec["prompt_lengths"]))


def _block_outputs(spec: dict, n: int) -> List[int]:
    outs = expand(spec["output_lengths"])
    if n % len(outs):
        raise ValueError(f"a block of {n} prompts does not hold a whole "
                         f"number of the {len(outs)} output lengths")
    return outs * (n // len(outs))


def shared_head(spec: dict, seed: int, vocab: int) -> np.ndarray:
    """The one shared head of the mix (empty when the file shares none)."""
    n = int((spec.get("shared_head") or {}).get("tokens", 0))
    rs = np.random.default_rng([int(seed), 0x5EED])
    return rs.integers(1, vocab + 1, n, dtype=np.int32)


def _order_rng(spec: dict, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(spec.get("order_seed", 0)), stream])


def requests(spec: dict, seed: int, vocab: int
             ) -> Iterator[Tuple[np.ndarray, int]]:
    """Endless sequence of ``(prompt, max_new)``: lengths one block of the
    multiset after another, each block permuted (and prompts paired with
    outputs) by the file's ``order_seed``; 1-based token ids drawn from
    ``seed``.  ``shared_head.every = k`` makes every k-th request of the
    sequence open with the mix's one shared head."""
    prompts = expand(spec["prompt_lengths"])
    outs = _block_outputs(spec, len(prompts))
    head = shared_head(spec, seed, vocab)
    every = int((spec.get("shared_head") or {}).get("every", 0))
    order = _order_rng(spec, 1)
    rs = np.random.default_rng([int(seed), 1])
    i = 0
    while True:
        for tp, out in zip(order.permutation(prompts),
                           order.permutation(outs)):
            p = rs.integers(1, vocab + 1, int(tp), dtype=np.int32)
            if every and i % every == 0 and head.size < tp:
                p[:head.size] = head
            yield p, int(out)
            i += 1


def due_times(spec: dict, n: int) -> np.ndarray:
    """Paced open loop: ``n`` due times (seconds from the schedule's
    start) evenly spaced at ``rate_per_s``, each moved later by a jitter
    (drawn from the file's ``order_seed``) of at most ``jitter_fraction``
    (default a fifth) of the gap."""
    gap = 1.0 / float(spec["rate_per_s"])
    frac = float(spec.get("jitter_fraction", 0.2))
    return (np.arange(n) + _order_rng(spec, 2).random(n) * frac) * gap


def initial_budgets(spec: dict, outputs: List[int]) -> List[int]:
    """Remaining output budgets for the requests that fill a closed
    loop's slots during set-up, so that it starts in its steady state:
    request k gets a stratified share ``(perm[k] + 0.5) / n`` of its
    output length (uniform over 1..length across the slots), at least 1;
    the permutation comes from the file's ``order_seed``."""
    n = len(outputs)
    perm = _order_rng(spec, 3).permutation(n)
    return [max(1, int(round(o * (int(perm[k]) + 0.5) / n)))
            for k, o in enumerate(outputs)]
