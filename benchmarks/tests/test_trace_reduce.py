"""The reduction's arithmetic, on hand-made tuples and on a small piece of a
trace recorded on the chip."""

import json
import os

import pytest

import trace_reduce as tr

DEV, HOST = '/device:TPU:0', '/host:CPU'
MS = 1e6  # nanoseconds


def ev(name, start_ms, dur_ms, line=tr.OPS_LINE, plane=DEV):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


HAND_MADE = [
    # two modules; the ops of the first overlap each other
    ev('jit_evaluate_packed(1)', 0, 30, tr.MODULES_LINE),
    ev('fusion.1', 0, 20), ev('gather.2', 10, 20),
    # a gap of 70 ms, then the second module
    ev('jit_other(2)', 100, 10, tr.MODULES_LINE),
    ev('fusion.1', 100, 10),
    ev('jit_evaluate_packed(1)', 200, 50, tr.MODULES_LINE),
    ev('gather.2', 200, 50),
    # the host: one span that covers the first gap
    ev('encode', 25, 80, 'thread-1', HOST),
]


def test_merged_joins_overlapping_and_touching_intervals():
    assert tr.merged([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_busy_is_the_union_of_op_intervals_not_their_sum():
    assert tr.busy_intervals(HAND_MADE, DEV) == [
        (0, 30 * MS), (100 * MS, 110 * MS), (200 * MS, 250 * MS)]
    assert tr.busy_seconds(HAND_MADE) == pytest.approx(0.090)
    assert tr.idle_share(0.090, 0.250) == pytest.approx(64.0)


def test_busy_is_averaged_over_the_chips_that_ran_anything():
    two = HAND_MADE + [ev('fusion.1', 0, 10, plane='/device:TPU:1')]
    assert tr.busy_seconds(two) == pytest.approx((0.090 + 0.010) / 2)
    assert tr.busy_seconds([e for e in HAND_MADE if e[0] == HOST]) is None


def test_module_seconds_takes_one_program_by_the_start_of_its_name():
    assert tr.module_seconds(HAND_MADE, 'jit_evaluate_packed') == \
        pytest.approx([0.030, 0.050])
    assert tr.module_seconds(HAND_MADE, 'jit_other') == pytest.approx([0.010])
    assert tr.module_seconds(HAND_MADE, 'jit_missing') == []


def test_top_ops_sums_by_name_longest_first():
    assert tr.top_ops(HAND_MADE, n=1) == [['gather.2', pytest.approx(0.070)]]
    assert [n for n, _s in tr.top_ops(HAND_MADE)] == ['gather.2', 'fusion.1']


def test_gaps_and_their_labels():
    window = tr.span_ns(HAND_MADE)
    assert window == (0, 250 * MS)
    busy = tr.busy_intervals(HAND_MADE, DEV)
    assert tr.gaps(busy, window) == [(30 * MS, 100 * MS),
                                     (110 * MS, 200 * MS)]
    # the host's 'encode' covers the whole first gap and little of the second
    assert tr.idle_by_label(HAND_MADE, window) == [
        ['unattributed', pytest.approx(0.090)],
        ['encode', pytest.approx(0.070)]]
    # gaps that share a label are summed
    twice = HAND_MADE + [ev('fusion.1', 300, 10),
                         ev('encode', 250, 50, 'thread-1', HOST)]
    assert tr.idle_by_label(twice, (0, 310 * MS))[0] == \
        ['encode', pytest.approx(0.120)]


def test_a_gap_at_either_end_of_the_window_counts():
    assert tr.gaps([(10, 20)], (0, 30)) == [(0, 10), (20, 30)]
    assert tr.gaps([], (0, 30)) == [(0, 30)]


def test_summarise_keeps_the_device_events_and_the_host_timed_window():
    s = tr.summarise(HAND_MADE, 0.5)
    assert s['busy_s'] == pytest.approx(0.090) and s['window_s'] == 0.5
    assert all(e[0] == DEV for e in s['events']) and len(s['events']) == 7
    assert len(s['device_ops']) <= 10 and len(s['idle_gaps']) <= 10


SAMPLE = os.path.join(os.path.dirname(__file__), 'trace_sample.json')


@pytest.mark.skipif(not os.path.exists(SAMPLE),
                    reason='no recorded sample beside this file')
def test_on_a_piece_of_a_trace_recorded_on_the_chip():
    """One run of the evaluator's program on a TPU v5 lite and the
    operations inside it (PR 25)."""
    with open(SAMPLE) as f:
        events = [tuple(e) for e in json.load(f)]
    runs = tr.module_seconds(events, 'jit_evaluate_packed')
    assert len(runs) == 1 and runs[0] > 0
    busy = tr.busy_seconds(events)
    # the operations run inside the program's run, and fill most of it
    assert 0.5 * runs[0] < busy <= runs[0] * 1.001
    assert tr.top_ops(events)[0][1] <= busy
