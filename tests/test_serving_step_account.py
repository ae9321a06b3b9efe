"""The step's own account (docs/OBSERVABILITY.md "What a step says of
itself"): where every lane stood at each tick's dispatch and why a free one
stayed free (``serving.decode``), what the step carried (``serving.tick``),
the rows a prefill call held, and the ``fleetx_serving_lane_steps_total``
counters beside the spans. On the CPU at tiny sizes; no scheduling decision
is tested here, only that the step says what it did."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import sharing_programs

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.obs import get_recorder
from fleetx_tpu.serving import ServingEngine, rows_in
from fleetx_tpu.serving.metrics import LANE_STATES, WAITS, lane_steps

LANES = 4
FIELDS = ("lanes_finishing", "lanes_prefilling", "lanes_waiting",
          "lanes_unasked")


@pytest.fixture(scope="module")
def model():
    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=2, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    net = GPTForPretraining(cfg)
    return net, jax.jit(net.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))


@sharing_programs
def _engine(net, params, **kwargs):
    kwargs.setdefault("slots", LANES)
    return ServingEngine(
        net, params, cache_len=32, prefill_bucket=4, page_size=8,
        gen_cfg=GenerationConfig(decode_strategy="greedy",
                                 eos_token_id=10**6, pad_token_id=60,
                                 max_length=6), **kwargs)


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _steps(engine, pending):
    """Step ``engine`` until it is drained, submitting of ``pending``
    (``(prompt, max_length)``) three before the first step and one before
    each later one; returns, a step, ``(its spans, the requests whose last
    token it delivered)``."""
    rec, finished, out = get_recorder(), [], []
    pending = list(pending)

    def on_token(rid, _token, last):
        if last:
            finished.append(rid)

    while (pending or len(engine.scheduler) or engine._active
           or engine._prefilling or engine._inflight is not None):
        for prompt, budget in pending[:1 if out else 3]:
            engine.submit(prompt, max_length=budget, on_token=on_token)
        del pending[:1 if out else 3]
        rec.clear()
        del finished[:]
        engine.step()
        out.append((rec.spans(), list(finished)))
    return out


@pytest.fixture(scope="module")
def run(model):
    """A chunked engine of four lanes offered more than a step admits:
    eleven requests, some behind a shared page of prompt, some of ONE
    token, three submitted at once and then one a step. Returns ``(engine,
    prompts, steps)``."""
    engine = _engine(*model, prefill_chunk=4, prefix_cache=True)
    engine._probed_at = float("inf")  # no admission sampled to be read at once
    rng = np.random.default_rng(7)
    shared = rng.integers(1, 60, 8, dtype=np.int32)
    prompts = []
    for n, budget, behind in [(5, 4, 0), (11, 3, 0), (3, 1, 0), (12, 5, 1),
                              (6, 2, 0), (14, 4, 1), (4, 3, 0), (9, 1, 0),
                              (13, 6, 1), (2, 2, 0), (7, 5, 0)]:
        tail = rng.integers(1, 60, n, dtype=np.int32)
        prompts.append((np.concatenate([shared, tail]) if behind else tail,
                        budget))
    return engine, prompts, _steps(engine, prompts)


def test_the_five_lane_fields_add_up_to_the_slots_on_every_tick(run):
    _, _, steps = run
    ticks = [s for spans, _ in steps for s in _named(spans, "serving.decode")]
    assert len(ticks) >= 15
    for tick in ticks:
        at = tick.attrs
        assert at["batch"] + sum(at[f] for f in FIELDS) == LANES, at
        assert all(at[f] >= 0 for f in FIELDS), at
        assert at["empty_lanes"] == LANES - at["batch"]
        # the cause is named exactly where a free lane has a request queued
        assert ("waiting_on" in at) == (at["lanes_waiting"] > 0), at
        assert at.get("waiting_on", "slot") in WAITS
    # more was offered than a step admits: lanes waited, and for the slot
    assert any(t.attrs["lanes_waiting"] for t in ticks)
    assert {t.attrs.get("waiting_on") for t in ticks} == {None, "slot"}
    # FIFO: one prompt mid-prefill at a time in an engine that decodes
    assert {t.attrs["lanes_prefilling"] for t in ticks} == {0, 1}
    assert any(t.attrs["lanes_unasked"] for t in ticks)


def test_a_requests_last_step_shows_its_lane_finishing(run):
    """The tick dispatched while a request's LAST token is unread carries
    the lane as ``finishing`` (it is the request's, and decodes nothing):
    as many as the step then delivers last tokens for."""
    _, prompts, steps = run
    seen = []
    for spans, finished in steps:
        for tick in _named(spans, "serving.decode"):
            assert tick.attrs["lanes_finishing"] == len(finished)
            seen += finished
    # among them one admitted for a single token, which is in no lane set
    # while its only token is unread (requests are numbered as submitted)
    assert len(seen) >= 4
    assert any(prompts[rid][1] == 1 for rid in seen)


def test_the_tick_says_what_the_step_carried(run):
    engine, prompts, steps = run
    ticks = [s for spans, _ in steps for s in _named(spans, "serving.tick")]
    snap = engine.metrics.snapshot()
    total = {key: sum(t.attrs[key] for t in ticks)
             for key in ("admitted", "chunked", "tower", "decoded",
                         "prefill_rows")}
    # the prompts' rows less what the trie served
    assert snap["prefill_tokens_saved"] == 16   # two hits of one page
    assert total["prefill_rows"] == (sum(len(p) for p, _ in prompts)
                                     - snap["prefill_tokens_saved"])
    assert total["admitted"] == len(prompts) and total["tower"] == 0
    assert total["chunked"] == snap["prefill_chunks"] - sum(
        1 for spans, _ in steps for s in _named(spans, "serving.prefill_chunk")
        if s.parent == "serving.admit")
    # every token but the admissions' first came from a tick
    assert total["decoded"] == sum(b for _, b in prompts) - len(prompts)
    # the rows of the calls are the same rows, call by call
    for spans, _ in steps:
        (tick,) = _named(spans, "serving.tick")
        calls = _named(spans, "serving.prefill")
        assert tick.attrs["prefill_rows"] == sum(
            c.attrs["rows"] for c in calls)
        assert all(0 < c.attrs["rows"] <= c.attrs["bucket"] for c in calls)
        for chunk in _named(spans, "serving.prefill_chunk"):
            (call,) = [c for c in calls if chunk.start_s <= c.start_s
                       and c.end_s <= chunk.end_s]
            assert chunk.attrs["rows"] == call.attrs["rows"] <= 4


def test_the_lane_step_counters_are_the_spans_sums(run):
    engine, _, steps = run
    ticks = [s.attrs for spans, _ in steps
             for s in _named(spans, "serving.decode")]
    want = dict.fromkeys(LANE_STATES, 0)
    for at in ticks:
        for state, lanes in lane_steps(at["batch"], at).items():
            want[state] += lanes
    snap = engine.metrics.snapshot()
    got = {state: snap["lane_steps_" + state] for state in LANE_STATES}
    assert got == want and len(LANE_STATES) == 7
    assert sum(got.values()) == LANES * len(ticks)
    # one family in the registry, a series a state
    text = engine.metrics.registry.prometheus_text()
    label = engine.metrics.engine_label
    for state in LANE_STATES:
        assert (f'fleetx_serving_lane_steps_total{{engine="{label}",'
                f'state="{state}"}} {got[state]}') in text


# ------------------------------------------------ why a free lane stayed free

def _mid_prefill(model):
    """A short request decodes, a long prompt is mid-prefill in chunks of
    four, two a step, a third is queued behind it with a lane free: the
    step's two prefill-shaped calls go to the head, which holds the slot."""
    engine = _engine(*model, prefill_chunk=4)
    rng = np.random.default_rng(1)
    engine.submit(rng.integers(1, 60, 3, dtype=np.int32), max_length=12)
    engine.step()
    engine.submit(rng.integers(1, 60, 29, dtype=np.int32), max_length=2)
    engine.submit(rng.integers(1, 60, 3, dtype=np.int32), max_length=2)
    engine.step()     # admits the long one: its first chunk, and its second
    return engine, 2, lambda: None


def _pool_too_small(model):
    """Five usable pages: the first request holds three and grows into a
    fourth, the head of the queue needs three."""
    engine = _engine(*model, slots=2, num_pages=6)
    rng = np.random.default_rng(2)
    engine.submit(rng.integers(1, 60, 20, dtype=np.int32), max_length=8)
    engine.step()
    engine.submit(rng.integers(1, 60, 20, dtype=np.int32), max_length=2)
    return engine, 3, lambda: None


def _worker_held(_model):
    """A tower whose worker is held at the head's first image: the request
    has no trie keys yet, and a lane is free."""
    from test_keyevl2_serving import session, tiny_model

    net, variables = tiny_model()
    engine = ServingEngine(
        net, variables, slots=3, cache_len=512, page_size=8,
        num_pages=3 * 64 + 1, prefill_chunk=32, prefill_bucket=16,
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=8))
    release, real = threading.Event(), rows_in.image_digest

    def waits(image):
        assert release.wait(timeout=60.0)
        return real(image)

    rows_in.image_digest = waits

    def let_go():
        rows_in.image_digest = real
        release.set()

    engine.submit(np.arange(1, 9, dtype=np.int32), max_length=8)
    engine.step()
    tokens, images = session(3, grids=((2, 3),), caption=4, tail=12)
    engine.submit(tokens[:-6], images=images, max_length=2)
    return engine, 3, let_go


@pytest.mark.parametrize("cause,scene", [
    ("slot", _mid_prefill), ("pages", _pool_too_small),
    ("keys", _worker_held)])
def test_waiting_on_names_what_refused_the_head(model, cause, scene):
    engine, steps, let_go = scene(model)
    before = engine.metrics.snapshot()
    rec = get_recorder()
    rec.clear()
    try:
        for _ in range(steps):
            engine.step()
        spans = rec.spans()
    finally:
        let_go()
    ticks = _named(spans, "serving.decode")
    assert len(ticks) == steps
    for tick in ticks:
        at = tick.attrs
        assert at["lanes_waiting"] == 1 and at["waiting_on"] == cause, at
        assert at["batch"] + sum(at[f] for f in FIELDS) == engine.slots
    checks = _named(spans, "serving.can_admit")
    seconds = engine.metrics.snapshot()["second_chunks"]
    if cause == "slot":
        # a prompt mid-prefill holds the head: nobody is even asked
        assert not checks
        assert all(t.attrs["lanes_prefilling"] == 1 for t in ticks)
        # and takes both of the step's calls, which the counter counts
        # (as it did the step that admitted it and read on)
        assert [t.attrs["chunked"] for t in _named(spans, "serving.tick")
                ] == [2] * steps
        assert seconds == before["second_chunks"] + steps == steps + 1
    else:
        assert seconds == before["second_chunks"]
        # said where it is decided, and handed on: not derived again
        assert [c.attrs.get("refused") for c in checks] == [cause] * steps
    snap = engine.metrics.snapshot()
    waited = {w: snap["lane_steps_waiting_" + w]
              - before["lane_steps_waiting_" + w] for w in WAITS}
    assert waited == {**dict.fromkeys(WAITS, 0), cause: steps}
    results = engine.drain()
    assert all(r.finish_reason == "max_length" for r in results.values())
    if engine._tower is not None:
        assert engine._tower.worker.join(60.0)
