"""Bytes of one dispatch from a hand-made packed layout, and the roofline
share that a reader makes of them."""

import pytest

import benchlib
import bytes as dispatch_bytes

# lanes as ops/eval.py pack_batch lays them out: (buffer, offset, width, tail)
LAYOUT = {'a': ('pk_int64', 0, 3, (3,)), 'b': ('pk_int64', 3, 5, (5,)),
          'c': ('pk_int8', 0, 2, (2,)), 'd': ('pk_int32', 0, 1, ())}


def test_argument_bytes_are_capacity_times_each_buffers_row_width():
    assert dispatch_bytes.row_widths(LAYOUT) == {
        'pk_int64': 8, 'pk_int8': 2, 'pk_int32': 1}
    assert dispatch_bytes.argument_bytes(LAYOUT, 64) == 64 * (64 + 2 + 4)


def test_output_bytes_are_the_two_output_buffers():
    # out8: statuses + details of 6 unique trees and 2 admission columns;
    # out32: columns and values of min(32, 9) fail-detail cells
    assert dispatch_bytes.output_bytes(64, 6, 9, 2, 32) == \
        64 * (2 * 6 + 2) + 64 * 2 * 9 * 4
    assert dispatch_bytes.output_bytes(64, 6, 40, 0, 32) == \
        64 * 12 + 64 * 2 * 32 * 4


def test_roofline_share_is_least_time_over_measured_time():
    reader = benchlib.load_module('readers', 'trace_roofline')
    run = ('/device:TPU:0', 'XLA Modules', 'jit_k(1)', 0.0, 2e6)  # 2 ms
    reading = {'counters': {'dispatch': {'bytes': 819e6}},
               'trace': {'events': [run]}, 'device_kind': 'TPU v5 lite',
               'peaks': {'hbm_bytes_per_s': 819e9}}
    # 819 MB at 819 GB/s is 1 ms, half of the 2 ms the program took
    assert reader.read(reading, module='jit_k', bytes='dispatch.bytes') == \
        pytest.approx(50.0)
    with pytest.raises(KeyError, match='no device kind'):
        reader.read(dict(reading, peaks=None, device_kind='TPU v9'),
                    module='jit_k', bytes='dispatch.bytes')
    assert reader.read(dict(reading, trace=None), module='jit_k',
                       bytes='dispatch.bytes') is None
