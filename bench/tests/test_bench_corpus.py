"""The vectorised generator keeps the statistics of the program's
line-by-line generator (``repro.logstore.datasets``)."""
import re

import numpy as np
import pytest

from bench import corpus as bench_corpus

N, SOURCES = 30_000, 48
_ID = re.compile(r"[a-z]{16}")


def _stats(lines, sources):
    counts = np.sort(np.bincount(sources, minlength=SOURCES))[::-1]
    ids = [m for line in lines for m in _ID.findall(line)]
    return {
        "top_share": counts[0] / len(lines),
        "top4_share": counts[:4].sum() / len(lines),
        "mean_len": float(np.mean([len(s) for s in lines])),
        "distinct_lines": len(set(lines)) / len(lines),
        "id_reuse": 1 - len(set(ids)) / max(len(ids), 1),
        "levels": {lv: sum(s.startswith(lv) for s in lines) / len(lines)
                   for lv in ("INFO", "WARN", "ERROR", "DEBUG")},
    }


@pytest.fixture(scope="module")
def both():
    from repro.logstore.datasets import generate_dataset
    prog = [generate_dataset("t", n_lines=N, n_sources=SOURCES, seed=s)
            for s in (3, 4)]
    mine = [bench_corpus.generate(n_lines=N, n_sources=SOURCES, seed=s)
            for s in (3, 4)]
    return ([_stats(d.lines, d.sources) for d in prog],
            [_stats(c.lines, c.sources) for c in mine])


def test_same_seed_same_lines():
    a = bench_corpus.generate(n_lines=2000, n_sources=20, seed=2**31 + 7)
    b = bench_corpus.generate(n_lines=2000, n_sources=20, seed=2**31 + 7)
    assert a.lines == b.lines
    assert np.all(np.diff(a.sources) >= 0)      # sorted by source
    c = bench_corpus.generate(n_lines=2000, n_sources=20, seed=8)
    assert a.lines != c.lines


def test_statistics_match_the_program_generator(both):
    prog, mine = both
    for key in ("top_share", "top4_share", "mean_len", "id_reuse"):
        lo = min(s[key] for s in prog)
        hi = max(s[key] for s in prog)
        for s in mine:
            # the two generators' seeds differ as much as either's own
            assert lo * 0.7 <= s[key] <= hi * 1.3, (key, s[key], lo, hi)
    for s in mine:
        assert s["distinct_lines"] > 0.9


def test_every_line_is_a_filled_template():
    c = bench_corpus.generate(n_lines=5000, n_sources=30, seed=1)
    assert all("{" not in line for line in c.lines)
    for line, t in zip(c.lines[:500], c.templates[:500]):
        parts, _ = bench_corpus._split(bench_corpus.TEMPLATES[t])
        assert line.startswith(parts[0]) and line.endswith(parts[-1])
    assert c.raw_bytes() == sum(len(x) + 1 for x in c.lines)
