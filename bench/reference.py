"""Plain reference for the log store's queries: a scan of every line.

The semantics of COPR §5 (tokenization rules 1-5 of §5.1.1), written
again from the paper and independent of the program:

  * ``term(q)``: the lines that hold ``q`` as a whole token, comparing
    lower case.  A token is a run of ASCII letters and digits (rule 1),
    a run of other printable ASCII that is not a space (rule 2), a run of
    non-ASCII characters (rule 3), two runs of rule 1 joined by one of
    ``.:-_/@`` (rule 4), or three runs of rule 1 joined by single dots
    (rule 5).
  * ``contains(q)``: the lines that hold ``q`` as a substring, comparing
    lower case.

Answers are sorted line ids.  The scan is split over worker processes
that import nothing but this module, so it holds no chip.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import re

ALNUM = re.compile(r"[0-9a-z]+")
PUNCT = re.compile(r"[!-/:-@\[-`{-~]+")
NONASCII = re.compile(r"[^\x00-\x7f]+")
JOINERS = ".:-_/@"
#: Below this many lines one process scans faster than a pool starts.
SERIAL_LINES = 100_000


def line_terms(lower: str) -> set[str]:
    """Every rule 1-5 token of one lower-cased line."""
    runs = [(m.start(), m.end()) for m in ALNUM.finditer(lower)]
    terms = {lower[a:b] for a, b in runs}
    terms.update(PUNCT.findall(lower))
    terms.update(NONASCII.findall(lower))
    for (a0, b0), (a1, b1) in zip(runs, runs[1:]):
        if a1 == b0 + 1 and lower[b0] in JOINERS:
            terms.add(lower[a0:b1])
    for (a0, b0), (a1, b1), (a2, b2) in zip(runs, runs[1:], runs[2:]):
        if (a1 == b0 + 1 and lower[b0] == "."
                and a2 == b1 + 1 and lower[b1] == "."):
            terms.add(lower[a0:b2])
    return terms


def _scan(args) -> tuple[dict, dict]:
    lines, first, terms, needles = args
    term_hits: dict = {}
    contains_hits: dict = {}
    for i, line in enumerate(lines, first):
        lower = line.lower()
        for q in line_terms(lower) & terms:
            term_hits.setdefault(q, []).append(i)
        for q in needles:
            if q in lower:
                contains_hits.setdefault(q, []).append(i)
    return term_hits, contains_hits


def answer(lines: list[str], terms, needles, *,
           workers: int | None = None) -> tuple[dict, dict]:
    """Reference answers: ``({term: ids}, {needle: ids})`` over ``lines``
    for every term query in ``terms`` and contains query in ``needles``
    (each given as the user typed it)."""
    terms = {q.lower() for q in terms}
    needles = sorted({q.lower() for q in needles})
    if workers is None:
        workers = 1 if len(lines) < SERIAL_LINES else min(8, os.cpu_count()
                                                            or 1)
    step = -(-len(lines) // (workers * 4)) or 1
    jobs = [(lines[a:a + step], a, terms, needles)
            for a in range(0, len(lines), step)]
    if workers == 1 or len(jobs) == 1:
        parts = [_scan(j) for j in jobs]
    else:
        with mp.get_context("spawn").Pool(workers) as pool:
            parts = pool.map(_scan, jobs)
    term_ans = {q: [] for q in terms}
    contains_ans = {q: [] for q in needles}
    for th, ch in parts:                 # chunks arrive in line order
        for q, ids in th.items():
            term_ans[q].extend(ids)
        for q, ids in ch.items():
            contains_ans[q].extend(ids)
    return term_ans, contains_ans
