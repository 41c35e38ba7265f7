"""Spans of the served query path, read back from a profiler trace.

A small durable store is served through ``StoreServer`` to 4 client
threads, once inside a profiler session and once outside.  The trace
must hold every span of ``repro.tracing``, each child inside its parent
on the parent's thread, request ids shared between the client's spans
and the wave worker's, the named device programs and no ``body``; the
answers must be the same with the profiler on and off.  A store's batch
LRU opens one decompress span per miss and none per hit.
"""
import glob
import os
import threading
import warnings

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core.tokenizer import contains_query_tokens
from repro.logstore.store import DynaWarpStore

TIMEOUT = 120
N_CLIENTS = 4
SPANS = (tracing.SERVE_QUEUE, tracing.SERVE_WAVE, tracing.POSTFILTER,
         tracing.POSTFILTER_DECOMPRESS, tracing.POSTFILTER_RETOKENIZE,
         tracing.WAVE, tracing.WAVE_SYNC)
#: child span -> its parent on the same thread
PARENT = {tracing.POSTFILTER_DECOMPRESS: tracing.POSTFILTER,
          tracing.POSTFILTER_RETOKENIZE: tracing.POSTFILTER,
          tracing.WAVE_SYNC: tracing.WAVE}
PROGRAMS = ("copr_probe", "copr_reduce", "copr_extract")


@pytest.fixture(scope="module")
def store(small_dataset, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("traced-store"))
    s = DynaWarpStore(path=path, batch_lines=64, mode="segmented",
                      memory_limit_bytes=1 << 14, auto_compact=False)
    s.ingest(small_dataset.lines)
    s.finish()
    s.close()
    s = DynaWarpStore.open(path)
    yield s
    s.close()


@pytest.fixture(scope="module")
def requests(small_dataset):
    """(op, text): present ids as terms and as substrings, common words."""
    from repro.logstore.datasets import present_id_queries
    ids = present_id_queries(small_dataset, 3, 4)
    reqs = [("term", t) for t in ids + ["info", "connection"]]
    reqs += [("contains", t[1:-1]) for t in ids]
    assert all(contains_query_tokens(t) for op, t in reqs
               if op == "contains")
    return reqs


def _serve(store, requests) -> dict:
    """Every request from each of N_CLIENTS threads; the answers by
    (op, text), checked equal across the clients."""
    answers: dict = {}
    errors: list = []
    lock = threading.Lock()
    with store.serving(n_replicas=2) as server:
        def client(c: int) -> None:
            try:
                for op, text in requests[c:] + requests[:c]:
                    fn = (server.query_term if op == "term"
                          else server.query_contains)
                    got = fn(text, timeout=TIMEOUT).matches
                    with lock:
                        assert answers.setdefault((op, text), got) == got
            except BaseException as e:      # reported by the test
                errors.append(e)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(N_CLIENTS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(TIMEOUT)
        assert not any(th.is_alive() for th in threads)
    assert not errors, errors
    return answers


@pytest.fixture(scope="module")
def traced(store, requests, tmp_path_factory):
    """(answers, host lines) of one served round inside a profiler
    session; each host line is (thread name, [(name, start, end,
    stats)])."""
    _serve(store, requests[:2])         # compile outside the session
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the program's spans, not Python's
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        answers = _serve(store, requests)
    finally:
        jax.profiler.stop_trace()
    return answers, _host_lines(log_dir)


def _host_lines(log_dir: str) -> list:
    """The host lines of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    lines = []
    with warnings.catch_warnings():     # event stats' type warns on read
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:"):
                continue
            for ln in plane.lines:
                lines.append((ln.name, [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in ln.events]))
    return lines


def _spans(lines, name):
    return [(thread, ev) for thread, events in lines for ev in events
            if ev[0] == name]


def test_every_span_appears(traced):
    _, lines = traced
    for name in SPANS:
        assert _spans(lines, name), name
    whats = {ev[3].get("what") for _, ev in _spans(lines, tracing.WAVE_SYNC)}
    assert whats == {"counts", "ids"}
    assert all(ev[3]["ascii"] in (0, 1)
               for _, ev in _spans(lines, tracing.POSTFILTER_DECOMPRESS))


def test_decompress_span_once_per_batch_cache_miss(tmp_path):
    """One ``copr.postfilter.decompress`` span per LRU miss and none on
    a hit, each with its batch's ``ascii`` stat."""
    from repro.logstore.store import ScanStore
    s = ScanStore(batch_lines=2, batch_cache_size=8)
    s.ingest(["INFO a", "WARN b", "caf\u00e9 c", "INFO d", "WARN e"])
    s.finish()
    cands = np.arange(s.n_batches)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        first = s.query_contains("info").matches    # every batch misses
        second = s._post_filter(cands, "info", "contains").matches  # hits
    finally:
        jax.profiler.stop_trace()
    assert first == second == [0, 3]
    spans = [ev for _, ev in _spans(_host_lines(str(tmp_path)),
                                    tracing.POSTFILTER_DECOMPRESS)]
    assert sorted(ev[3]["ascii"] for ev in spans) == [0, 1, 1]


def test_each_child_lies_within_its_parent_on_its_thread(traced):
    _, lines = traced
    for thread, events in lines:
        for child, parent in PARENT.items():
            outer = [(s, e) for n, s, e, _ in events if n == parent]
            for n, s, e, _ in events:
                if n == child:
                    assert any(a <= s and e <= b for a, b in outer), \
                        (thread, child, s, e)


def test_client_spans_share_wave_ids_with_the_worker(traced):
    _, lines = traced
    waves = {ev[3]["wave"] for _, ev in _spans(lines, tracing.WAVE)}
    client = [ev for name in (tracing.SERVE_QUEUE, tracing.SERVE_WAVE,
                              tracing.POSTFILTER,
                              tracing.POSTFILTER_DECOMPRESS,
                              tracing.POSTFILTER_RETOKENIZE)
              for _, ev in _spans(lines, name)]
    assert client
    for ev in client:
        assert {"query", "wave"} <= set(ev[3]), ev
        assert ev[3]["wave"] in waves, ev
    # one query id per served request, its queue, wave and post-filter
    # spans carrying the same wave id
    by_query: dict = {}
    for name in (tracing.SERVE_QUEUE, tracing.SERVE_WAVE,
                 tracing.POSTFILTER):
        for _, ev in _spans(lines, name):
            by_query.setdefault(ev[3]["query"], {})[name] = ev[3]["wave"]
    assert all(len(set(w.values())) == 1 and len(w) == 3
               for w in by_query.values())


def test_device_programs_are_named(traced):
    _, lines = traced
    names = {ev[0] for _, events in lines for ev in events}
    for prog in PROGRAMS:
        assert f"PjitFunction({prog})" in names, prog
    assert not any("(body)" in n for n in names), \
        sorted(n for n in names if "body" in n)


def test_answers_are_the_same_without_a_profiler_session(
        store, requests, traced):
    assert not tracing.enabled()
    answers, _ = traced
    assert _serve(store, requests) == answers
    for op, text in requests:
        direct = (store.query_term(text) if op == "term"
                  else store.query_contains(text))
        assert answers[(op, text)] == direct.matches, (op, text)
    assert any(answers.values())
