"""The query ops the harness drives, in one table.

An op is what the harness needs of a query beyond its text:

  * ``tokens(text) -> list``: the program's query tokens (the warm-up
    groups the pool's shapes by their count);
  * ``send(server, text, timeout) -> QueryResult``: one request through
    the ``StoreServer``'s own threads;
  * ``answer(lines, texts) -> {text: sorted line ids}``: the plain
    reference over every line.

``term`` and ``contains`` are built in, and answered together by one
scan of ``bench/reference.py``.  A scenario module under
``bench/queries/`` whose ``OP`` is neither brings the three functions
itself.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

from . import reference


class Op(NamedTuple):
    tokens: Callable
    send: Callable
    answer: Callable | None     # None: a built-in op, see ``answers``


def _term_tokens(text: str) -> list:
    from repro.core.tokenizer import term_query_tokens
    return term_query_tokens(text)


def _contains_tokens(text: str) -> list:
    from repro.core.tokenizer import contains_query_tokens
    return contains_query_tokens(text)


BUILTIN = {
    "term": Op(_term_tokens,
               lambda server, text, timeout: server.query_term(
                   text, timeout=timeout),
               None),
    "contains": Op(_contains_tokens,
                   lambda server, text, timeout: server.query_contains(
                       text, timeout=timeout),
                   None),
}


def of_module(mod) -> Op:
    """The op of a scenario module: a built-in one, or its own."""
    return BUILTIN.get(mod.OP) or Op(mod.tokens, mod.send, mod.answer)


def answers(table: dict[str, Op], lines, want) -> dict:
    """Reference answers ``{(op, text): ids}`` for every pair in
    ``want``: the built-in ops in one scan, every other op by its own
    ``answer``."""
    terms = sorted({t for o, t in want if o == "term"})
    needles = sorted({t for o, t in want if o == "contains"})
    out = {}
    if terms or needles:
        term_ans, contains_ans = reference.answer(lines, terms, needles)
        out.update({("term", t): term_ans[t.lower()] for t in terms})
        out.update({("contains", t): contains_ans[t.lower()]
                    for t in needles})
    for name, op in table.items():
        texts = sorted({t for o, t in want if o == name})
        if op.answer is not None and texts:
            out.update({(name, t): ids
                        for t, ids in op.answer(lines, texts).items()})
    return out
