"""The readers of the program's spans, on a small hand-made trace: what
each reads, the window it keeps to, None for a program without spans,
and the raise when an instrumented program's span is missing."""
import importlib.util
import os

import pytest

from bench import trace
from bench.harness import Run
from bench.loops import Record

MS = 1e6
METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader(stem):
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{stem}", os.path.join(METRICS, f"{stem}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _trace():
    """A 100 ms window.  Two client threads: queue waits of 10 and 30
    ms, a post-filter of 40 ms holding two misses of 5 and 15 ms and one
    of 20 ms holding none; the wave worker runs waves of 2 and 6 ms.
    Spans ending after the window's close are left out, and with a
    post-filter so left out, the miss inside it that ended before the
    close (92-96 ms).  On the device,
    probe modules at 10-14 ms and 98-102 ms hold ops covering 10.5-12,
    13.5-14 (of an op running on into the reduce module) and 98-100 ms
    (clipped at the close): 2 + 2 ms of probe time in the window."""
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "bench", "events": [
                [trace.WINDOW_SPAN, 0.0, 100 * MS]]},
            {"name": "client-0", "events": [
                ["copr.serve.queue", 0.0, 10 * MS],
                ["copr.serve.wave", 10 * MS, 5 * MS],
                ["copr.postfilter", 15 * MS, 40 * MS],
                ["copr.postfilter.decompress", 16 * MS, 5 * MS],
                ["copr.postfilter.decompress", 30 * MS, 15 * MS],
                ["copr.serve.queue", 95 * MS, 10 * MS]]},
            {"name": "client-1", "events": [
                ["copr.serve.queue", 20 * MS, 30 * MS],
                ["copr.serve.wave", 50 * MS, 5 * MS],
                ["copr.postfilter", 55 * MS, 20 * MS],
                ["copr.postfilter", 90 * MS, 20 * MS],
                ["copr.postfilter.decompress", 92 * MS, 4 * MS]]},
            {"name": "wave-worker-0", "events": [
                ["copr.wave", 10 * MS, 2 * MS],
                ["copr.wave.sync", 11 * MS, 1 * MS],
                ["copr.wave", 44 * MS, 6 * MS],
                ["copr.wave", 99 * MS, 6 * MS]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_copr_probe(7)", 10 * MS, 4 * MS],
                ["jit_copr_reduce(8)", 14 * MS, 2 * MS],
                ["jit_copr_probe(7)", 98 * MS, 4 * MS]]},
            {"name": trace.OPS_LINE, "events": [
                ["gather.1", 10.5 * MS, 1 * MS],
                ["copy.2", 11 * MS, 1 * MS],
                ["fusion.3", 13.5 * MS, 1.5 * MS],
                ["bitset_reduce_batch_pallas.4", 15 * MS, 0.5 * MS],
                ["copy.2", 98 * MS, 4 * MS]]}]},
    ]}


def _run(t, *, waves=2, t_end=110.0):
    """Answers at 100.5 s (before the window), at 109.95 and 109.98 s
    (inside it: the window is [109.9, 110] s on the host clock; 3 and 5
    candidate batches), and at 111 s (after it)."""
    recs = [Record(0, 100.0, done=t_end - 9.5, candidates=50),
            Record(0, 100.0, done=t_end - 0.05, candidates=3),
            Record(0, 100.0, done=t_end - 0.02, candidates=5),
            Record(0, 100.0, done=t_end + 1.0, candidates=7)]
    return Run(cell="c", config={}, traffic={}, queries=[], n_tokens=[],
               records=recs, stats={"waves": waves, "device_waves": waves},
               n_batches=64, device_kind="x", t0=t_end - 0.1, t_end=t_end,
               trace=t)


def _without(t, name):
    for p in t["planes"]:
        for ln in p["lines"]:
            ln["events"] = [e for e in ln["events"] if e[0] != name]
    return t


READS = {
    # (10 + 30) / 2: the wait that ends after the close is left out
    "queue_wait_ms_per_query": 20.0,
    # (2 + 6) / 2
    "wave_host_ms_per_wave": 4.0,
    # (40 + 20) / 2
    "postfilter_ms_per_query": 30.0,
    # (5 + 15) / 2 post-filters
    "decompress_ms_per_query": 10.0,
    # 2 misses over the 3 + 5 candidates answered inside the window
    "batch_cache_miss_share": 25.0,
    # (1.5 + 0.5 + 2) ms of probe ops over 2 device waves
    "probe_device_ms_per_wave": 2.0,
}
#: the event each reader needs, and the name its error gives
NEEDS = {"queue_wait_ms_per_query": ("copr.serve.queue",) * 2,
         "wave_host_ms_per_wave": ("copr.wave",) * 2,
         "postfilter_ms_per_query": ("copr.postfilter",) * 2,
         "decompress_ms_per_query": ("copr.postfilter",) * 2,
         "batch_cache_miss_share": ("copr.postfilter",) * 2,
         "probe_device_ms_per_wave": ("jit_copr_probe(7)", "copr_probe")}


@pytest.mark.parametrize("stem", sorted(READS))
def test_reads_its_spans_inside_the_window(stem):
    assert _reader(stem)(_run(_trace())) == pytest.approx(READS[stem])


@pytest.mark.parametrize("stem", sorted(READS))
def test_missing_span_raises(stem):
    event, named = NEEDS[stem]
    with pytest.raises(RuntimeError, match=named):
        _reader(stem)(_run(_without(_trace(), event)))


@pytest.mark.parametrize("stem", sorted(READS))
def test_program_without_spans_reads_nothing(stem):
    t = _trace()
    for p in t["planes"]:
        for ln in p["lines"]:
            ln["events"] = [e for e in ln["events"]
                            if not e[0].startswith("copr.")]
    assert _reader(stem)(_run(t)) is None
    assert _reader(stem)(_run(None)) is None


def test_no_miss_reads_zero():
    t = _without(_trace(), "copr.postfilter.decompress")
    assert _reader("decompress_ms_per_query")(_run(t)) == 0.0
    assert _reader("batch_cache_miss_share")(_run(t)) == 0.0


def test_no_waves_and_no_span_reads_nothing():
    t = _without(_trace(), "copr.wave")
    assert _reader("wave_host_ms_per_wave")(_run(t, waves=0)) is None
    t = _without(_trace(), "jit_copr_probe(7)")
    assert _reader("probe_device_ms_per_wave")(_run(t, waves=0)) is None
