"""The trace reduction, on a small trace recorded on one TPU v5e.

`data/criteo-1chip.xplane.pb.gz` is the traced window of a run of the
criteo-1chip cell cut to n = 8,192 with a 0.5-s window (two solves of
four epochs), recorded on the chip.
"""
import gzip
import os

import pytest

from chipbench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "criteo-1chip.xplane.pb.gz")


@pytest.fixture(scope="module")
def reading():
    with gzip.open(FIXTURE, "rb") as f:
        return trace.reduce_trace(trace.load(f.read()))


def test_one_device_and_a_window(reading):
    assert [d.name for d in reading.devices] == ["/device:TPU:0"]
    assert reading.window_s > 0


def test_busy_and_idle_partition_the_window(reading):
    dev = reading.devices[0]
    assert 0 < dev.busy_s <= reading.window_s
    idle = sum(reading.idle_by_span.values())
    assert idle == pytest.approx(reading.window_s - dev.busy_s, rel=1e-9)
    assert set(reading.idle_by_span) <= {"bench.window", "bench.solve",
                                         "bench.reset", "bench.epoch",
                                         "bench.gap"}


def test_the_sparse_kernel_is_found(reading):
    k = reading.kernel_s("sdca_sparse_bucket_kernel")
    assert 0 < k <= reading.busy_s
    assert reading.kernel_s("sdca_bucket_kernel") == 0
    ops = dict(reading.breakdown()["device_ops"])
    assert ops["sdca_sparse_bucket_kernel"] == pytest.approx(k)


def test_no_collectives_on_one_chip(reading):
    assert reading.devices[0].collective_s == 0


def test_breakdown_is_short(reading):
    b = reading.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s >= 0 for n, s in
               b["device_ops"] + b["idle_gaps"])


def test_interval_arithmetic():
    u = trace._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [[0, 3], [5, 9]]
    assert trace._subtract(u, [[1, 2], [4, 6]]) == [[0, 1], [2, 3], [6, 9]]
    assert trace._subtract([[0, 10]], []) == [[0, 10]]
    assert trace._measure(u) == 7


def test_idle_time_goes_to_the_innermost_span():
    spans = [(0, 100, "bench.solve"), (10, 20, "bench.epoch"),
             (30, 40, "bench.gap"), (200, 210, "bench.reset")]
    assert trace._spans_at(spans, [5, 15, 35, 50, 150, 205]) == [
        "bench.solve", "bench.epoch", "bench.gap", "bench.solve",
        "bench.window", "bench.reset"]


def test_kernel_names():
    text = ('%vmap_jit_sdca_sparse_bucket_kernel__.1 = (f32[8]) custom-call('
            's32[8] %a), custom_call_target="tpu_custom_call", x')
    assert trace.kernel_name(text) == "sdca_sparse_bucket_kernel"
    assert trace.kernel_name('%custom-call.3 = f32[8] custom-call(), '
                             'custom_call_target="ConcatBitcast"') is None
    assert trace.op_name("%fusion.12 = f32[8] fusion(%a)") == "fusion.12"
