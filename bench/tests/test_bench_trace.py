"""The reduction from a profiler trace to busy time, idle share, kernel
time and the breakdown, on a small hand-made trace."""
import pytest

from bench import trace

MS = 1e6


def _trace():
    """A 10 ms window; the device runs ops at 1-3 ms (a kernel), 2-4 ms
    (overlapping) and 8-12 ms (clipped at 10 ms); the host is in
    ``wait`` from 4 to 8 ms."""
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                [trace.WINDOW_SPAN, 0.0, 10 * MS],
                ["wait", 4 * MS, 4 * MS]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_body", 0.0, 10 * MS]]},
            {"name": trace.OPS_LINE, "events": [
                ["bitset_reduce_batch_pallas.1", 1 * MS, 2 * MS],
                ["fusion.1", 2 * MS, 2 * MS],
                ["bitmap_extract_pallas.3", 8 * MS, 4 * MS],
                ["before", -5 * MS, 1 * MS]]}]},
    ]}


def test_busy_is_the_union_of_ops_inside_the_window():
    t = _trace()
    assert trace.window_s(t) == pytest.approx(0.010)
    # [1, 4] and [8, 10] ms; the module line and the op before are ignored
    assert trace.busy_s(t) == pytest.approx(0.005)


def test_kernel_time_and_top_ops():
    t = _trace()
    assert trace.op_seconds(t, lambda n: "bitset_reduce_batch_pallas" in n) \
        == pytest.approx(0.002)
    assert trace.op_seconds(t, lambda n: "bitmap_extract_pallas" in n) \
        == pytest.approx(0.002)
    top = dict(trace.top_ops(t))
    assert set(top) == {"bitset_reduce_batch_pallas.1", "fusion.1",
                        "bitmap_extract_pallas.3"}


def test_idle_gaps_go_to_what_the_host_was_doing():
    gaps = dict(trace.idle_gaps(_trace()))
    assert gaps["wait"] == pytest.approx(0.004)
    assert gaps["no host event"] == pytest.approx(0.001)
    assert sum(gaps.values()) == pytest.approx(0.005)


def test_no_window_span_is_an_error():
    t = _trace()
    t["planes"][0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError):
        trace.window(t)
