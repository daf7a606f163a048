"""Whole-step and whole-chunk arithmetic, percentiles, lateness."""

import pytest

from benchmark import stats


def _stamps(period, n, t0=0.0):
    return [t0 + i * period for i in range(n)]


def test_whole_step_rate_counts_only_whole_steps():
    stamps = _stamps(0.1, 101)                    # a step every 100 ms
    rate, steps = stats.whole_step_rate(stamps, 1.0, 9.0, per_step=256)
    assert steps == 80
    assert rate == pytest.approx(2560.0)


@pytest.mark.parametrize("edge", [8.91, 8.95, 8.999, 9.0, 9.05, 9.0999])
def test_window_edge_that_cuts_a_step_changes_nothing(edge):
    stamps = _stamps(0.1, 101)
    whole, _ = stats.whole_step_rate(stamps, 1.0, 9.0, per_step=256)
    cut, _ = stats.whole_step_rate(stamps, 1.0 - (9.0 - edge) / 2, edge,
                                   per_step=256)
    assert cut == pytest.approx(whole)


def test_whole_step_rate_is_not_steps_over_window():
    # 10 steps of 1 s inside an 10.9 s window: a count over the window
    # would read 9.2% low; between stamps it is exact
    stamps = _stamps(1.0, 12)
    rate, steps = stats.whole_step_rate(stamps, 0.05, 10.95, per_step=1)
    assert steps == 9 and rate == pytest.approx(1.0)


def test_whole_step_rate_needs_two_stamps():
    assert stats.whole_step_rate([1.0], 0.0, 2.0, 1) == (None, 0)
    assert stats.whole_step_rate([], 0.0, 2.0, 1) == (None, 0)


def test_whole_chunk_rate_between_changes():
    # a chunk every 1.5 s emits 128 tokens; prefills add 1 in between
    changes, tok = [], 0
    for i in range(20):
        tok += 128
        changes.append((1.5 * (i + 1), tok))
        if i % 3 == 0:
            tok += 1
            changes.append((1.5 * (i + 1) + 0.2, tok))
    rate, units = stats.whole_chunk_rate(changes, 3.0, 24.0)
    inside = [(t, v) for t, v in changes if 3.0 <= t <= 24.0]
    assert units == inside[-1][1] - inside[0][1]
    assert rate == pytest.approx(units / (inside[-1][0] - inside[0][0]))


@pytest.mark.parametrize("edge", [24.0, 24.3, 25.0, 25.49])
def test_window_edge_that_cuts_a_chunk_changes_nothing(edge):
    changes = [(1.5 * (i + 1), 128 * (i + 1)) for i in range(20)]
    whole, _ = stats.whole_chunk_rate(changes, 3.0, 24.0)
    cut, _ = stats.whole_chunk_rate(changes, 3.0, edge)
    assert cut == pytest.approx(whole) == pytest.approx(128 / 1.5)


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([3, 1, 2], 100) == 3
    assert stats.percentile([], 95) is None
    # the program's own arithmetic, which this copies
    from bigdl_tpu.observability.report import _percentile
    for q in (1, 50, 90, 95, 99):
        assert stats.percentile(vals, q) == _percentile(sorted(vals), q)


def test_lateness_is_send_less_due_and_never_negative():
    assert stats.lateness_ms([1.002, 2.0, 2.9], [1.0, 2.0, 3.0]) == \
        pytest.approx([2.0, 0.0, 0.0])
