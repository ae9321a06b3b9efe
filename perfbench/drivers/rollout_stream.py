"""The request stream of ``rollout-skewed``: short task prompts whose ids
are SKEWED BY TOPIC, which ``perfbench/traffic.py`` cannot make (it draws
ids uniformly from the vocabulary).

Closed loop: :func:`client_stream` is the endless request sequence of one
client. ``topics.count`` topics each own a fixed permutation of the ids
``[1, vocab)``, drawn from the traffic file's ``order_seed`` (the same for
every client and every ``--seed``). A request's topic is drawn with weight
``k ^ -weight_exponent`` (``k`` = 1 .. count), and its ids are the topic's
ids at RANKS drawn Zipf, ``p(r) ~ r ^ -zipf_exponent`` over ``r`` = 1 ..
vocab - 1 (bounded: the inverse of the cumulative weights). Prompt and
output lengths hold the quantiles of their distributions once in every block
of ``block`` (``traffic.stratified_lengths``, as ``docqa_stream`` draws its). Topics, lengths and ranks are
a function of ``order_seed`` and the client ALONE, never of timing and not
of ``--seed``: some 200 requests fill a window, and neither which lengths
fall into it nor which ids a prompt routes must be the seed's to choose (the
seed draws the weights). Ids are drawn from ``[1, vocab)``: a sliced
vocabulary is a smaller vocabulary.
"""

from __future__ import annotations

import functools
from typing import Iterator

import numpy as np

from perfbench.drivers.docqa_stream import _lengths
from perfbench.traffic import Request


def topic_weights(topics: dict) -> np.ndarray:
    """The topics' probabilities: ``k ^ -weight_exponent``, normalised."""
    w = np.arange(1, int(topics["count"]) + 1, dtype=np.float64) ** -float(
        topics.get("weight_exponent", 1.0))
    return w / w.sum()


@functools.lru_cache(maxsize=4)
def _tables(order_seed: int, count: int, exponent: float, vocab: int):
    """``(ids [count, vocab - 1], cdf [vocab - 1])``: each topic's ids by
    rank, and the cumulative Zipf weights of the ranks."""
    rng = np.random.default_rng([order_seed, 2])
    ids = np.stack([rng.permutation(vocab - 1) + 1 for _ in range(count)])
    weights = np.arange(1, vocab, dtype=np.float64) ** -exponent
    return ids.astype(np.int32), np.cumsum(weights) / weights.sum()


def zipf_ranks(rng: np.random.Generator, cdf: np.ndarray, n: int):
    """``n`` ranks (0-based) drawn by the cumulative weights ``cdf``."""
    return np.minimum(np.searchsorted(cdf, rng.random(n)), len(cdf) - 1)


def client_stream(traffic: dict, seed: int, client: int,
                  vocab: int) -> Iterator[Request]:
    """Module docstring. The request's ``tenant`` names its topic
    (``topic3``). ``seed`` is taken, as every stream's is, and not used."""
    del seed
    block, topics = int(traffic.get("block", 4)), traffic["topics"]
    order_seed = int(traffic["order_seed"])
    order = np.random.default_rng([order_seed, 1, client])
    ids, cdf = _tables(order_seed, int(topics["count"]),
                       float(topics.get("zipf_exponent", 1.1)), int(vocab))
    weights = topic_weights(topics)
    prompts = _lengths(order, traffic["prompt"], block)
    outputs = _lengths(order, traffic["output"], block)
    index = 0
    while True:
        topic = int(order.choice(len(weights), p=weights))
        ranks = zipf_ranks(order, cdf, int(next(prompts)))
        yield Request(index, 0.0, f"topic{topic}", ids[topic, ranks],
                      int(next(outputs)))
        index += 1
