"""The plain reference against the store's served answers on a tiny
store, and its tokenization against the paper's rules."""
import numpy as np
import pytest

from bench import corpus as bench_corpus
from bench import reference, scenarios


def test_line_terms_follow_rules_1_to_5():
    got = reference.line_terms("ip 10.1.2.3:80 blk_ab-c x@y ..!")
    assert {"ip", "10", "1", "2", "3", "80", "blk", "ab", "c", "x", "y",
            ".", ":", "_", "-", "@", "..!"} <= got
    assert {"10.1", "1.2", "2.3", "3:80", "blk_ab", "ab-c", "x@y",
            "10.1.2", "1.2.3"} <= got
    assert "2.3:80" not in got and "10.1.2.3" not in got
    assert "é" in reference.line_terms("café")


def test_reference_equals_program_tokenizer():
    from repro.core.tokenizer import tokenize_line
    c = bench_corpus.generate(n_lines=3000, n_sources=20, seed=5)
    for line in c.lines:
        want = {t.decode() for t in tokenize_line(line, ngrams=False)}
        assert reference.line_terms(line.lower()) == want


def test_pool_workers_give_the_serial_answers():
    c = bench_corpus.generate(n_lines=4000, n_sources=20, seed=6)
    terms = ["info", "alice", "blk", "zzzz"]
    needles = ["3.4", "request_id=a"]
    serial = reference.answer(c.lines, terms, needles, workers=1)
    pooled = reference.answer(c.lines, terms, needles, workers=2)
    assert serial == pooled
    assert serial[0]["info"] == sorted(serial[0]["info"])
    assert serial[0]["zzzz"] == []


@pytest.fixture(scope="module")
def tiny_store():
    from repro.logstore.store import DynaWarpStore
    c = bench_corpus.generate(n_lines=6000, n_sources=24, seed=9)
    store = DynaWarpStore(batch_lines=64, mode="segmented",
                          memory_limit_bytes=1 << 17)
    store.ingest(c.lines)
    store.finish()
    return c, store


def test_reference_equals_served_answers(tiny_store):
    c, store = tiny_store
    traffic = {"pool": {"term_id": 4, "present_term_id": 4, "term_ip": 4,
                        "present_term_ip": 4, "contains_ip": 4,
                        "contains_id": 4, "term_extracted": 4},
               "small_sources": 12, "extracted_lines": 150}
    queries, _ = scenarios.pool(traffic, c, seed=3)
    terms = [t for _, op, t in queries if op == "term"]
    needles = [t for _, op, t in queries if op == "contains"]
    term_ans, contains_ans = reference.answer(c.lines, terms, needles)
    found = 0
    with store.serving(flush_deadline_s=0.001) as server:
        for scen, op, text in queries:
            fn = server.query_term if op == "term" else server.query_contains
            got = sorted(fn(text, timeout=120).matches)
            want = (term_ans if op == "term" else contains_ans)[text.lower()]
            assert got == want, (scen, text)
            found += bool(want)
    assert found >= 12      # present, extracted and contains queries hit
