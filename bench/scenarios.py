"""The paper's query scenarios, and the query pools a traffic file draws.

Copies of ``repro.logstore.datasets``' query generators and of
``benchmarks/common.QUERY_SCENARIOS`` (COPR §5.2, Table 3), kept here so
the yardstick cannot move with the program:

  * ``term_id``: random 16-letter ids (needles, nearly always absent);
  * ``term_ip``: random partial IPs of three octets, as terms;
  * ``present_term_id`` / ``present_term_ip``: ids and IP prefixes taken
    from lines of the smallest sources, so they are present and their
    lines lie in few batches;
  * ``contains_ip``: partial IPs cut across token borders (``q[2:-1]``):
    short numeric n-grams, the sketches' worst case;
  * ``contains_id``: 12-letter slices (``q[2:14]``) of ids that occur in
    lines of the smallest sources;
  * ``term_extracted``: tokens of 4 to 24 letters and digits taken from
    the lines, those whose line count (estimated on a sample) lies nearest
    the traffic's ``extracted_lines``, so that every seed's pool costs
    about the same.

A query is ``(scenario, op, text)``.  The seven scenarios above use the
built-in ops ``"term"`` and ``"contains"`` (``bench/ops.py``).  A pool
entry of any other name is the module ``bench/queries/<name>.py``, which
gives ``OP``, the name of its op, and ``draw(rng, corpus, n, traffic)``,
``n`` distinct query texts; for an op that is not built in it also gives
the op's ``tokens``, ``send`` and ``answer`` (``bench/ops.py``).
"""
from __future__ import annotations

import math
import re

import numpy as np

from . import byname, ops
from .corpus import Corpus, random_ids
from .reference import ALNUM, line_terms

OPS = {"term_id": "term", "term_ip": "term", "present_term_id": "term",
       "present_term_ip": "term", "term_extracted": "term",
       "contains_ip": "contains", "contains_id": "contains"}

_ID = re.compile(r"(?<![0-9a-z])[a-z]{16}(?![0-9a-z])")
_IP = re.compile(r"(?<![0-9.])(\d{1,3}\.\d{1,3}\.\d{1,3})\.\d{1,3}(?![0-9.])")
_SAMPLE_LINES = 20_000


def _partial_ips(rng, n: int) -> list[str]:
    octets = rng.integers(1, 255, size=(n, 3)).astype(str)
    return [".".join(row) for row in octets.tolist()]


def _small_source_lines(corpus: Corpus, n_sources: int) -> np.ndarray:
    """Line ids of the ``n_sources`` sources with the fewest lines."""
    counts = np.bincount(corpus.sources)
    live = np.flatnonzero(counts)
    small = live[np.argsort(counts[live], kind="stable")[:n_sources]]
    return np.flatnonzero(np.isin(corpus.sources, small))


def _from_lines(rng, corpus: Corpus, line_ids, pattern, group: int,
                n: int) -> list[str]:
    found: list[str] = []
    for i in rng.permutation(line_ids).tolist():
        for m in pattern.finditer(corpus.lines[i].lower()):
            found.append(m.group(group))
        if len(set(found)) >= 4 * n:
            break
    uniq = list(dict.fromkeys(found))
    return [uniq[int(k)] for k in rng.permutation(len(uniq))[:n]]


def _extracted(rng, corpus: Corpus, n: int, target: float) -> list[str]:
    """Tokens of 4 to 24 letters and digits taken from the lines (the
    paper's rule), the ``n`` whose line count, estimated on a sample of
    the corpus, lies nearest ``target``."""
    sample = rng.choice(corpus.n_lines, size=min(_SAMPLE_LINES,
                                                 corpus.n_lines),
                        replace=False)
    counts: dict[str, int] = {}
    for i in sample.tolist():
        for t in line_terms(corpus.lines[i].lower()):
            if 4 <= len(t) <= 24 and ALNUM.fullmatch(t):
                counts[t] = counts.get(t, 0) + 1
    scale = corpus.n_lines / sample.size
    terms = sorted(counts)
    tie = rng.random(len(terms))
    order = sorted(range(len(terms)), key=lambda k: (
        abs(math.log(counts[terms[k]] * scale / target)), tie[k]))
    return [terms[k] for k in order[:n]]


def draw(scenario: str, rng, corpus: Corpus, n: int, traffic: dict
         ) -> list[str]:
    """``n`` distinct query texts of one scenario."""
    if scenario == "term_id":
        return list(dict.fromkeys(random_ids(rng, 2 * n)))[:n]
    if scenario == "term_ip":
        return list(dict.fromkeys(_partial_ips(rng, 2 * n)))[:n]
    if scenario == "contains_ip":
        return list(dict.fromkeys(q[2:-1] for q in _partial_ips(rng, 2 * n)
                                  if len(q[2:-1]) >= 3))[:n]
    small = _small_source_lines(corpus, traffic.get("small_sources", 64))
    if scenario == "present_term_id":
        return _from_lines(rng, corpus, small, _ID, 0, n)
    if scenario == "present_term_ip":
        return _from_lines(rng, corpus, small, _IP, 1, n)
    if scenario == "contains_id":
        return [q[2:14] for q in _from_lines(rng, corpus, small, _ID, 0, n)]
    if scenario == "term_extracted":
        return _extracted(rng, corpus, n, traffic["extracted_lines"])
    raise ValueError(f"unknown scenario {scenario!r}")


def module(scenario: str):
    """The module of a scenario this file does not define."""
    return byname.load("queries", scenario)


def module_files(traffic: dict) -> list[str]:
    """The files of the scenario modules the traffic's pool names."""
    return [byname.path("queries", s) for s in traffic["pool"]
            if s not in OPS]


def pool(traffic: dict, corpus: Corpus, seed: int
         ) -> tuple[list[tuple], dict[str, ops.Op]]:
    """Every distinct query of the traffic's ``pool``, scenario by
    scenario in the file's order, and the ops they use by name.  The
    texts are drawn from ``seed``, or from the traffic's ``pool_seed``
    where it fixes one pool for every seed."""
    rng = np.random.default_rng([traffic.get("pool_seed", seed), 1])
    queries: list[tuple] = []
    table: dict[str, ops.Op] = {}
    for scenario, n in traffic["pool"].items():
        if scenario in OPS:
            op = OPS[scenario]
            table[op] = ops.BUILTIN[op]
            texts = draw(scenario, rng, corpus, int(n), traffic)
        else:
            mod = module(scenario)
            op = mod.OP
            table[op] = ops.of_module(mod)
            texts = mod.draw(rng, corpus, int(n), traffic)
        if len(texts) < int(n):
            raise ValueError(f"{scenario}: {len(texts)} distinct queries, "
                             f"the traffic asks for {n}")
        queries += [(scenario, op, t) for t in texts]
    return queries, table
