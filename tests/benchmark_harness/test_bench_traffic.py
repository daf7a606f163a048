"""Every traffic file offers the same work under every seed."""

import collections
import glob
import os

import numpy as np
import pytest

from benchmark import cells, traffic

ROOT = cells.repo_root()
FILES = sorted(glob.glob(os.path.join(ROOT, "benchmark", "traffic", "*.json")))
SEEDS = (0, 7, 2 ** 31 + 12345)


def _take(spec, seed, n, vocab=50257):
    gen = traffic.requests(spec, seed, vocab)
    return [next(gen) for _ in range(n)]


def test_there_are_traffic_files():
    assert len(FILES) >= 3


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_file_loads_and_names_a_kind(path):
    spec = traffic.load(path)
    assert spec["kind"] in traffic.KINDS and spec["why"]


@pytest.mark.parametrize("path", [p for p in FILES
                                  if traffic.load(p)["kind"] != "train"],
                         ids=os.path.basename)
def test_two_seeds_offer_the_same_multiset_and_count(path):
    spec = traffic.load(path)
    n = 3 * traffic.block_size(spec)
    seen = []
    for seed in SEEDS:
        reqs = _take(spec, seed, n)
        assert len(reqs) == n
        seen.append((collections.Counter(len(p) for p, _ in reqs),
                     collections.Counter(o for _, o in reqs),
                     sum(len(p) for p, _ in reqs)))
        # every block holds exactly one copy of the multiset
        b = traffic.block_size(spec)
        for k in range(3):
            assert sorted(len(p) for p, _ in reqs[k * b:(k + 1) * b]) == \
                sorted(traffic.expand(spec["prompt_lengths"]))
        for p, _ in reqs:
            assert p.dtype == np.int32 and p.min() >= 1 and p.max() <= 50257
    assert all(s == seen[0] for s in seen)
    # ... in the same order (which lengths meet in the slots or in the
    # queue is the work), with other tokens
    a, b_ = _take(spec, SEEDS[0], n), _take(spec, SEEDS[1], n)
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b_]
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, b_))
    # the order is the file's own: another order_seed, another order
    other = _take(dict(spec, order_seed=99), SEEDS[0], n)
    if len(set(traffic.expand(spec["prompt_lengths"]))) > 1:
        assert [len(p) for p, _ in a] != [len(p) for p, _ in other]
    # the same seed gives the same requests
    again = _take(spec, SEEDS[0], n)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, again))


def test_shared_head_opens_every_second_prompt_of_chat_closed():
    spec = traffic.load(traffic.path_for(ROOT, "chat_closed"))
    head = traffic.shared_head(spec, 5, 50257)
    assert head.size == 64
    reqs = _take(spec, 5, 32)
    shared = [np.array_equal(p[:64], head) for p, _ in reqs]
    assert shared == [i % 2 == 0 for i in range(32)]


def test_score_open_shares_nothing_and_asks_one_token():
    spec = traffic.load(traffic.path_for(ROOT, "score_open"))
    reqs = _take(spec, 1, 64)
    assert {o for _, o in reqs} == {1}
    assert traffic.shared_head(spec, 1, 50257).size == 0
    lengths = sorted(traffic.expand(spec["prompt_lengths"]))
    assert lengths[0] == 64 and lengths[-1] == 768
    assert 190 <= np.median(lengths) <= 230


@pytest.mark.parametrize("order_seed", (0, 23, 99))
def test_due_times_are_paced_with_bounded_jitter(order_seed):
    spec = {"rate_per_s": 5.0, "jitter_fraction": 0.2,
            "order_seed": order_seed}
    due = traffic.due_times(spec, 100)
    gap = 0.2
    base = np.arange(100) * gap
    assert np.all(due >= base) and np.all(due < base + 0.2 * gap + 1e-12)
    assert np.all(np.diff(due) > 0)
    assert np.array_equal(due, traffic.due_times(spec, 100))


def test_initial_budgets_are_stratified_over_the_output_lengths():
    outs = [64, 128, 128, 192, 256, 64, 128, 192] * 4
    for order_seed in (0, 23):
        b = traffic.initial_budgets({"order_seed": order_seed}, outs)
        assert len(b) == 32 and all(1 <= x <= o for x, o in zip(b, outs))
        fractions = sorted(x / o for x, o in zip(b, outs))
        # spread evenly over (0, 1): no two slots end together
        assert fractions[0] < 0.1 and fractions[-1] > 0.9
        assert max(np.diff(fractions)) < 0.1
