"""Trace reduction on a small trace recorded on one TPU v5e.

The fixture is the profiler trace of a 1 s window of the sift1m-7bit
configuration at N = 100,000 (26 Q = 16 requests). Reading it needs only
JAX's trace parser; nothing here starts a backend.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from squashbench import traces  # noqa: E402

FIXTURE = os.path.join(HERE, "data", "v5e_7bit_n100k.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    return traces.reduce(traces.load(FIXTURE))


def test_window_busy_and_idle(reduced):
    assert reduced.chips == 1
    assert reduced.window_s == pytest.approx(1.006, abs=0.01)
    assert 0 < reduced.busy_s < reduced.window_s
    assert 0.5 < reduced.idle_share < 1.0


def test_kernel_time_by_instruction_name(reduced):
    ham = reduced.kernel_seconds("packed_hamming_stacked")
    adc = reduced.kernel_seconds("adc_lb_distances_batch")
    assert ham is not None and adc is not None
    assert 0 < ham < adc < reduced.busy_s
    # The fusion that reads the ADC output names the kernel as an operand;
    # it is not counted as the kernel.
    assert reduced.op_seconds["jit_plane/adc_lb_distances_batch.1"] == adc
    assert reduced.kernel_seconds("no_such_kernel") is None
    assert sum(reduced.op_seconds.values()) >= reduced.busy_s


def test_breakdown_lists(reduced):
    assert 0 < len(reduced.device_ops) <= 10
    assert 0 < len(reduced.idle_gaps) <= 10
    assert reduced.device_ops[0][0] == "jit_plane/adc_lb_distances_batch.1"
    secs = [s for _, s in reduced.device_ops]
    assert secs == sorted(secs, reverse=True)
    assert all(name.startswith("bench.") or name == "other"
               for name, _ in reduced.idle_gaps)


def test_interval_helpers():
    assert traces.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert traces.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert traces.gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    assert traces.instruction("jit_plane/sort.1") == "sort"
    assert traces.op_key("%fusion.6 = f32[160] fusion(%adc.1)", "jit_x") == \
        "jit_x/fusion.6"
