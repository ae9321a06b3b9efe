"""The one general traffic generator. A traffic mix is a data file under
``perfbench/traffic/``; this module turns its parameters and ``--seed``
into inputs, bit-identically for one seed. The program receives only the
generated inputs, never the seed.

Arrival and length shapes are copied from ``fleetx_tpu/serving/
workload.py`` ``generate_trace`` (seeded Poisson and Weibull gaps, burst
windows, tenants by weight, per-tenant shared prefixes drawn first), with
lognormal lengths given as median, sigma and clip range, as the traffic
files state them.

Keys of a traffic file (serving): ``arrivals`` (``rate_per_s``, the mean
rate outside bursts; ``process`` ``poisson`` or ``weibull`` with ``shape``;
optional ``burst_every_s`` / ``burst_len_s`` / ``burst_factor``) for an
open loop, or ``clients`` and ``block`` for a closed one; ``tenants``, each
with ``weight``, ``prompt`` and ``output`` length specs and
``shared_prefix_len``. A length spec is
``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
``{"dist": "uniform", "min", "max"}`` or ``{"dist": "fixed", "value"}``.
Training: ``documents`` is a length spec and ``tokens`` the file's size.

Every seed offers the same amount of work, so that runs on different seeds
agree (with free counts and lengths the offered load itself swung by 12%
from seed to seed: PERF.md, Findings, PR 22). An open loop offers exactly
the expected number of requests in the ramp and in the window each; the
gaps between them are drawn from the arrival process and rescaled to fill
the segment (for ``poisson`` that is a Poisson process given its count),
and bursts compress the gaps that fall into them. Lengths come in blocks
that hold the distribution's quantiles once each, in an order drawn from
the seed (``stratified_lengths``): the same multiset for every seed, and
only order and timing differ. A closed loop draws each client's lengths
from such blocks of ``block`` requests. Where a few dozen long requests
fill a window, the ORDER alone moves what the window completes (the
long-document cell: 6% from seed to seed with no device in the loop,
``simulate_closed_loop.py``): such a file gives ``order_seed``, which
fixes tenants and lengths for every ``--seed``; the seed then draws the
prompts' tokens (and the weights) alone.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
import statistics
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Request:
    """One generated request (host data only)."""

    index: int
    due_s: float          # open loop: seconds from the start of the trace
    tenant: str
    prompt: np.ndarray    # [prompt_len] int32 in [1, vocab)
    max_new_tokens: int


def length_bounds(spec: dict) -> tuple:
    """``(shortest, longest)`` length a length spec can give."""
    if spec["dist"] == "fixed":
        return int(spec["value"]), int(spec["value"])
    return int(spec["min"]), int(spec["max"])


def draw_length(rng: np.random.Generator, spec: dict) -> int:
    """One length from a length spec (module docstring)."""
    dist = spec["dist"]
    if dist == "fixed":
        return int(spec["value"])
    lo, hi = int(spec["min"]), int(spec["max"])
    if dist == "uniform":
        return int(rng.integers(lo, hi + 1))
    if dist == "lognormal":
        value = rng.lognormal(math.log(spec["median"]), spec["sigma"])
        return int(min(max(round(value), lo), hi))
    raise ValueError(f"unknown length distribution {dist!r}")


def quantile_length(spec: dict, q: float) -> int:
    """The length at quantile ``q`` (0 < q < 1) of a length spec."""
    dist = spec["dist"]
    if dist == "fixed":
        return int(spec["value"])
    lo, hi = int(spec["min"]), int(spec["max"])
    if dist == "uniform":
        return min(lo + int(q * (hi - lo + 1)), hi)
    if dist == "lognormal":
        z = statistics.NormalDist().inv_cdf(q)
        value = spec["median"] * math.exp(spec["sigma"] * z)
        return int(min(max(round(value), lo), hi))
    raise ValueError(f"unknown length distribution {dist!r}")


def stratified_lengths(rng: np.random.Generator, spec: dict, n: int) -> list:
    """``n`` lengths holding the quantiles (i + 1/2)/n once each, in an
    order drawn from ``rng``: the same multiset for every seed."""
    values = [quantile_length(spec, (i + 0.5) / n) for i in range(n)]
    return [values[i] for i in rng.permutation(n)]


def _burst_seconds(t, arrivals: dict):
    """Seconds of ``[0, t]`` that lie inside a burst (the first
    ``burst_len_s`` of every ``burst_every_s``)."""
    every = arrivals.get("burst_every_s") or 0.0
    if every <= 0:
        return 0.0 * t
    length = min(arrivals.get("burst_len_s", 0.0), every)
    return np.floor(t / every) * length + np.minimum(t % every, length)


def _offered(t, arrivals: dict):
    """Requests expected in ``[0, t]``: ``rate_per_s``, times
    ``burst_factor`` inside bursts."""
    extra = arrivals.get("burst_factor", 1.0) - 1.0
    return arrivals["rate_per_s"] * (t + extra * _burst_seconds(t, arrivals))


def _arrival_times(rng: np.random.Generator, arrivals: dict, a: float,
                   b: float) -> np.ndarray:
    """The due times in ``[a, b)``: as many as are expected there, with
    gaps drawn from the arrival process and rescaled to fill the segment
    in units of expected requests, which bursts run through faster."""
    every = arrivals.get("burst_every_s") or 0.0
    edges = [a, b]
    if every > 0:  # where the rate changes
        starts = np.arange(math.floor(a / every),
                           math.ceil(b / every) + 1) * every
        edges += [*starts, *(starts + arrivals.get("burst_len_s", 0.0))]
    edges = np.unique(np.clip(edges, a, b))
    offered = _offered(edges, arrivals)
    n = round(offered[-1] - offered[0])
    process = arrivals["process"]
    if process == "poisson":
        gaps = rng.exponential(1.0, n + 1)
    elif process == "weibull":
        gaps = rng.weibull(arrivals["shape"], n + 1)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    at = offered[0] + (offered[-1] - offered[0]) * (
        np.cumsum(gaps)[:n] / gaps.sum())
    return np.interp(at, offered, edges)


def _prefixes(rng, tenants, vocab):
    # drawn FIRST so that more requests never reshuffle what earlier share
    return {t["name"]: rng.integers(1, vocab, t["shared_prefix_len"],
                                    dtype=np.int32)
            for t in tenants if t.get("shared_prefix_len")}


def _one_request(rng, index, due_s, tenant, prefixes, vocab, plen,
                 new) -> Request:
    prefix = prefixes.get(tenant["name"])
    if prefix is not None:
        plen = max(plen, len(prefix) + 1)  # at least one fresh token
        prompt = np.concatenate([prefix, rng.integers(
            1, vocab, plen - len(prefix), dtype=np.int32)])
    else:
        prompt = rng.integers(1, vocab, plen, dtype=np.int32)
    return Request(index, due_s, tenant["name"], prompt, new)


def _weights(tenants) -> np.ndarray:
    w = np.asarray([t.get("weight", 1.0) for t in tenants], np.float64)
    return w / w.sum()


def open_loop_trace(traffic: dict, seed: int, duration_s: float,
                    vocab: int, ramp_s: float = 0.0) -> List[Request]:
    """Every request due in ``[0, duration_s)``, in order of arrival. The
    first ``ramp_s`` are the ramp: the count is fixed there and in the
    window separately, tenants come in fixed proportion and lengths are
    stratified (module docstring)."""
    arrivals, tenants = traffic["arrivals"], traffic["tenants"]
    rng = np.random.default_rng([seed, 0])
    prefixes = _prefixes(rng, tenants, vocab)
    weights = _weights(tenants)
    out: List[Request] = []
    for a, b in ((0.0, ramp_s), (ramp_s, duration_s)):
        if b <= a:
            continue
        due = _arrival_times(rng, arrivals, a, b)
        n = len(due)
        share = np.floor(weights * n).astype(int)
        share[: n - share.sum()] += 1  # the remainder to the first tenants
        who = rng.permutation(np.repeat(np.arange(len(tenants)), share))
        lengths = [(iter(stratified_lengths(rng, t["prompt"], int(k))),
                    iter(stratified_lengths(rng, t["output"], int(k))))
                   for t, k in zip(tenants, share)]
        for t_due, ti in zip(due, who):
            out.append(_one_request(
                rng, len(out), float(t_due), tenants[ti], prefixes, vocab,
                next(lengths[ti][0]), next(lengths[ti][1])))
    return out


def client_stream(traffic: dict, seed: int, client: int,
                  vocab: int) -> Iterator[Request]:
    """Closed loop: the endless request sequence of one client, a function
    of the seed and the client's index alone (never of timing). With
    ``order_seed`` in the traffic file, tenants and lengths are a function
    of that and the client's index, and the seed draws the tokens."""
    tenants = traffic["tenants"]
    prefixes = _prefixes(np.random.default_rng([seed, 0]), tenants, vocab)
    rng = np.random.default_rng([seed, 1, client])
    order = rng if traffic.get("order_seed") is None else \
        np.random.default_rng([int(traffic["order_seed"]), 1, client])
    weights = _weights(tenants)
    block = int(traffic.get("block", 16))
    index, queues = 0, {}
    while True:
        ti = int(order.choice(len(tenants), p=weights))
        tenant = tenants[ti]
        if not queues.get(ti):  # this tenant's next block of lengths
            queues[ti] = list(zip(
                stratified_lengths(order, tenant["prompt"], block),
                stratified_lengths(order, tenant["output"], block)))
        plen, new = queues[ti].pop()
        yield _one_request(rng, index, 0.0, tenant, prefixes, vocab, plen, new)
        index += 1


def trace_hash(requests: List[Request]) -> str:
    """16 hex digits naming exactly these inputs (arrivals to the
    microsecond, prompts byte for byte, budgets)."""
    h = hashlib.sha256()
    for r in requests:
        h.update(np.int64(round(r.due_s * 1e6)).tobytes())
        h.update(r.tenant.encode())
        h.update(np.ascontiguousarray(r.prompt, np.int32).tobytes())
        h.update(np.int64(r.max_new_tokens).tobytes())
    return h.hexdigest()[:16]


def token_documents(traffic: dict, seed: int, vocab: int):
    """Training: ``(ids, lens)`` of a token file of about
    ``traffic["tokens"]`` tokens in documents whose lengths follow
    ``traffic["documents"]`` (heavy-tailed, as web text is)."""
    rng = np.random.default_rng([seed, 2])
    total, spec = int(traffic["tokens"]), traffic["documents"]
    lens = []
    while sum(lens) < total:
        lens.append(draw_length(rng, spec))
    lens = np.asarray(lens, np.int32)
    ids = rng.integers(0, vocab, int(lens.sum()), dtype=np.int32)
    return ids, lens
