"""The request stream of ``docqa-latent``: documents asked several
questions each, which ``perfbench/traffic.py`` cannot make (its tenants
share ONE fixed prefix; here every client keeps taking NEW documents).

Closed loop: :func:`client_stream` is the endless request sequence of one
client, a function of (``--seed``, client) for the TOKENS and of the traffic
file's ``order_seed`` and the client for the LENGTHS, never of timing. A
client takes a document, asks ``questions`` questions of it one after the
other (each prompt is the document plus a question of its own), then takes
the next document. Client ``i`` asks ``questions - (i mod questions)``
questions of its FIRST document and ``questions`` of every later one, so
that the clients' cold prefills do not fall together. Document lengths are
whole pages; question and output lengths hold the quantiles of their
distributions once in every block of ``block`` (``traffic.
stratified_lengths``), in an order drawn from ``order_seed``. Ids are drawn
from ``[1, vocab)``: a sliced vocabulary is a smaller vocabulary.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

from perfbench.traffic import Request, stratified_lengths


def _lengths(order: np.random.Generator, spec: dict, block: int):
    while True:
        yield from stratified_lengths(order, spec, block)


def document_pages(traffic: dict, length: int) -> int:
    """``length`` rounded to whole pages, inside the document's clip."""
    page = int(traffic["page"])
    lo, hi = traffic["document"]["min"], traffic["document"]["max"]
    return int(min(max(round(length / page) * page, -(-lo // page) * page),
                   hi // page * page))


def client_stream(traffic: dict, seed: int, client: int,
                  vocab: int) -> Iterator[Request]:
    """Module docstring. The request's ``tenant`` names its document and
    question (``doc3.q1``)."""
    block, questions = int(traffic.get("block", 4)), int(traffic["questions"])
    tokens = np.random.default_rng([seed, 1, client])
    order = np.random.default_rng([int(traffic["order_seed"]), 1, client])
    docs = _lengths(order, traffic["document"], block)
    asked = _lengths(order, traffic["question"], block)
    outputs = _lengths(order, traffic["output"], block)
    index = doc = 0
    while True:
        document = tokens.integers(
            1, vocab, document_pages(traffic, next(docs)), dtype=np.int32)
        first = client % questions if doc == 0 else 0
        for q in range(first, questions):
            question = tokens.integers(1, vocab, next(asked), dtype=np.int32)
            yield Request(index, 0.0, f"doc{doc}.q{q}",
                          np.concatenate([document, question]),
                          int(next(outputs)))
            index += 1
        doc += 1
