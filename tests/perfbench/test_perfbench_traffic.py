"""The traffic generator: the same seed gives the same inputs, bit for
bit; another seed gives others with the same amount of work; lengths keep
to their clip range; every arrival key of a traffic file is honoured."""

import json
import os

import numpy as np
import pytest

from perfbench import harness, traffic

TRAFFIC_DIR = os.path.join(harness.HERE, "traffic")
OPEN_LOOP = [f[:-5] for f in sorted(os.listdir(TRAFFIC_DIR))
             if "arrivals" in json.load(open(os.path.join(TRAFFIC_DIR, f)))]
CLOSED_LOOP = [f[:-5] for f in sorted(os.listdir(TRAFFIC_DIR))
               if "clients" in json.load(open(os.path.join(TRAFFIC_DIR, f)))]


def _load(name):
    return harness.load_json("perfbench", "traffic", name + ".json")


@pytest.mark.parametrize("name", OPEN_LOOP)
def test_open_loop_trace_is_a_function_of_the_seed(name):
    job = _load(name)
    a = traffic.open_loop_trace(job, 7, 30.0, 50304)
    b = traffic.open_loop_trace(job, 7, 30.0, 50304)
    c = traffic.open_loop_trace(job, 8, 30.0, 50304)
    assert traffic.trace_hash(a) == traffic.trace_hash(b)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert traffic.trace_hash(a) != traffic.trace_hash(c)
    # arrivals in order, inside the duration, exactly as many as the rate
    # says, and the same multiset of lengths whatever the seed
    due = [r.due_s for r in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 30.0
    assert len(a) == len(c) == round(30.0 * job["arrivals"]["rate_per_s"])
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in c)
    for tenant in job["tenants"]:
        for r in a:
            assert tenant["prompt"]["min"] <= len(r.prompt) <= tenant["prompt"]["max"]
            assert tenant["output"]["min"] <= r.max_new_tokens <= tenant["output"]["max"]
            assert r.prompt.dtype == np.int32 and r.prompt.min() >= 1


@pytest.mark.parametrize("name", OPEN_LOOP)
def test_open_loop_rate_is_a_stated_share_of_a_swept_knee(name):
    """An open-loop cell judged on its tails runs at about four fifths of
    the highest rate the system sustained in a sweep on the chip: the file
    says what that knee was, which share of it the rate is, and which PR
    swept it, so that a later PR that moves the knee can be told from one
    that did not."""
    job = _load(name)
    rate, knee = job["arrivals"]["rate_per_s"], job["knee_per_s"]
    assert 0.7 <= rate / knee <= 0.85
    assert rate == pytest.approx(job["knee_share"] * knee)
    assert job["knee_swept_by"].startswith("PR ")


@pytest.mark.parametrize("name", CLOSED_LOOP)
def test_client_stream_depends_on_seed_and_client_only(name):
    job = _load(name)

    def head(seed, client, n=5):
        stream = traffic.client_stream(job, seed, client, 50304)
        return traffic.trace_hash([next(stream) for _ in range(n)])

    assert head(3, 0) == head(3, 0)
    assert head(3, 0) != head(3, 1)
    assert head(3, 0) != head(4, 0)


@pytest.mark.parametrize("name", CLOSED_LOOP)
def test_order_seed_pins_tenants_and_lengths_and_leaves_the_seed_the_tokens(
        name):
    """Where a window holds a few dozen long requests their ORDER alone
    moves what it completes, so such a file pins it; every other file's
    streams are what they were without the key."""
    job = _load(name)

    def head(job, seed, client, n=9):
        stream = traffic.client_stream(job, seed, client, 50304)
        return [next(stream) for _ in range(n)]

    def sizes(requests):
        return [(r.tenant, len(r.prompt), r.max_new_tokens) for r in requests]

    pinned = dict(job, order_seed=job.get("order_seed", 5))
    a, b = head(pinned, 3, 1), head(pinned, 4, 1)
    assert sizes(a) == sizes(b) != sizes(head(pinned, 3, 2))
    assert sizes(a) != sizes(head(dict(pinned, order_seed=6), 3, 1))
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    free = {k: v for k, v in job.items() if k != "order_seed"}
    assert sizes(head(free, 3, 1)) != sizes(head(free, 4, 1))
    assert ("order_seed" in job) == (name == "longdoc-gen")


def test_lengths_follow_their_spec():
    rng = np.random.default_rng(0)
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 64, "max": 768}
    draws = [traffic.draw_length(rng, spec) for _ in range(4000)]
    assert min(draws) >= 64 and max(draws) <= 768
    assert 230 < np.median(draws) < 285
    assert traffic.draw_length(rng, {"dist": "fixed", "value": 9}) == 9
    uniform = [traffic.draw_length(rng, {"dist": "uniform", "min": 8, "max": 32})
               for _ in range(500)]
    assert set(uniform) == set(range(8, 33))
    with pytest.raises(ValueError):
        traffic.draw_length(rng, {"dist": "zipf", "min": 1, "max": 2})


def test_ramp_and_window_each_hold_their_count():
    job = _load(OPEN_LOOP[0])
    rate = job["arrivals"]["rate_per_s"]
    trace = traffic.open_loop_trace(job, 2, 50.0, 50304, ramp_s=10.0)
    assert sum(r.due_s < 10.0 for r in trace) == round(10.0 * rate)
    assert sum(r.due_s >= 10.0 for r in trace) == round(40.0 * rate)


def test_weibull_gaps_are_burstier_and_bursts_hold_their_share():
    base = {"tenants": _load(OPEN_LOOP[0])["tenants"]}
    poisson = dict(base, arrivals={"process": "poisson", "rate_per_s": 20.0})
    weibull = dict(base, arrivals={"process": "weibull", "shape": 0.6,
                                   "rate_per_s": 20.0})
    bursty = dict(base, arrivals={"process": "poisson", "rate_per_s": 20.0,
                                  "burst_every_s": 10.0, "burst_len_s": 2.0,
                                  "burst_factor": 3.0})
    due = {k: np.asarray([r.due_s for r in
                          traffic.open_loop_trace(j, 1, 200.0, 1000)])
           for k, j in (("p", poisson), ("w", weibull), ("b", bursty))}
    # the mean rate holds whatever the process; Weibull gaps of shape 0.6
    # vary more than exponential ones (coefficient of variation 1.76 to 1)
    assert len(due["p"]) == len(due["w"]) == 4000
    cv = {k: np.diff(due[k]).std() / np.diff(due[k]).mean() for k in "pw"}
    assert 0.9 < cv["p"] < 1.1 and 1.5 < cv["w"] < 2.1
    # a fifth of the time at three times the rate: 1.4 times the requests,
    # three sevenths of them inside the bursts
    assert len(due["b"]) == 5600
    inside = (due["b"] % 10.0 < 2.0).mean()
    assert abs(inside - 3 / 7) < 0.03
    with pytest.raises(ValueError):
        traffic.open_loop_trace(dict(base, arrivals={
            "process": "gamma", "rate_per_s": 1.0}), 1, 10.0, 1000)


def test_closed_loop_blocks_hold_every_quantile_once():
    job = _load(CLOSED_LOOP[0])
    block = job["block"]
    spec = job["tenants"][0]
    want = sorted(traffic.quantile_length(spec["output"], (i + 0.5) / block)
                  for i in range(block))
    stream = traffic.client_stream(job, 9, 2, 50304)
    for _ in range(3):
        got = sorted(next(stream).max_new_tokens for _ in range(block))
        assert got == want


def test_shared_prefix_is_shared_and_token_file_is_seeded():
    job = {"arrivals": {"process": "poisson", "rate_per_s": 10.0},
           "tenants": [{"name": "t", "shared_prefix_len": 16,
                        "prompt": {"dist": "uniform", "min": 20, "max": 40},
                        "output": {"dist": "fixed", "value": 4}}]}
    trace = traffic.open_loop_trace(job, 5, 5.0, 1000)
    assert len(trace) > 10
    assert all(np.array_equal(r.prompt[:16], trace[0].prompt[:16]) for r in trace)
    assert len({tuple(r.prompt[16:20]) for r in trace}) > 5
    docs = {"tokens": 5000, "documents": {"dist": "lognormal", "median": 60,
                                          "sigma": 1.0, "min": 8, "max": 400}}
    ids, lens = traffic.token_documents(docs, 1, 512)
    ids2, lens2 = traffic.token_documents(docs, 1, 512)
    ids3, _ = traffic.token_documents(docs, 2, 512)
    assert np.array_equal(ids, ids2) and np.array_equal(lens, lens2)
    assert lens.sum() == len(ids) >= 5000 and ids.max() < 512
    assert not np.array_equal(ids[:100], ids3[:100])
