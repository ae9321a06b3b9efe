"""A step's prefill budget is TWO chunk-shaped calls (``serving/engine.py``
``_step_inner``, docs/SERVING.md "Chunked prefill"): the pass that takes
one (a chunk of the prompt mid-prefill, else one admission from the queue's
head) runs twice. The prompt mid-prefill is still the admission head and
still the only one: it is read at two chunks a step, and the next request
is admitted in the step its last chunk runs in, if a call is left. Host
scheduling alone: the second call is a second CALL of the bucket's program,
so nothing here may change a token, the order in which requests leave the
queue, or the programs an engine traces. On the CPU at tiny sizes, over
three families of cache: one class of page (``plain``), a window class
beside it (``window``: SmallThinker's block), and state held once a lane
(``lane_state``: Solar-Open2's delta rule)."""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import sharing_programs

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.obs import get_recorder
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.serving import ServingEngine
from fleetx_tpu.utils.compile_cache import enable_compile_cache

LANES, NEW = 3, 4
SAMPLED = dict(decode_strategy="sampling", temperature=0.9, top_k=8)


def _stirred(model, times):
    """Seeded weights whose layers decide the tokens (at the initializer's
    0.02 the head alone does, and a wrong cache row would change none)."""
    v = flax.core.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))

    def stir(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), x.shape)
        return x * times if "layers" in name and "kernel" in name else x

    return jax.tree_util.tree_map_with_path(stir, v)


def _plain():
    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=2, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=128,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    net = GPTForPretraining(cfg)
    return net, _stirred(net, 8.0), dict(
        cache_len=96, page_size=8, prefill_chunk=8, prefill_bucket=4), 61


def _window():
    from test_smallthinker_serving import CACHE_LEN, CHUNK, PAGE, build

    net = build()
    return net, _stirred(net, 8.0), dict(
        cache_len=CACHE_LEN, page_size=PAGE, prefill_chunk=CHUNK,
        prefill_bucket=8), 512


def _lane_state():
    from test_solar2_serving import BUCKET, CACHE_LEN, CHUNK, PAGE, build

    net = build()
    return net, _stirred(net, 2.0), dict(
        cache_len=CACHE_LEN, page_size=PAGE, prefill_chunk=CHUNK,
        prefill_bucket=BUCKET, prefix_cache=False), 256


@sharing_programs
def _engine(net, variables, **kw):
    kw.setdefault("slots", LANES)
    return ServingEngine(
        net, variables,
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=NEW), **kw)


class Family:
    """One family's model and engine settings, and three prompts of ONE
    length, four chunks and a last part each."""

    def __init__(self, name, made):
        self.name = name
        self.net, self.variables, self.kw, vocab = made()
        self.chunk = self.kw["prefill_chunk"]
        rng = np.random.default_rng(70)
        self.prompts = [rng.integers(1, vocab, 4 * self.chunk + 5,
                                     dtype=np.int32) for _ in range(LANES)]

    def engine(self, **kw):
        engine = _engine(self.net, self.variables, **{**self.kw, **kw})
        engine._probed_at = float("inf")   # no admission read at once
        return engine


@pytest.fixture(scope="module", params=[
    ("plain", _plain), ("window", _window), ("lane_state", _lane_state)],
    ids=lambda p: p[0])
def family(request):
    return Family(*request.param)


@pytest.fixture(scope="module")
def plain():
    return Family("plain", _plain)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _busy(engine):
    return (len(engine.scheduler) or engine._active or engine._prefilling
            or engine._inflight is not None)


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _drive(engine, prompts, **how):
    """Submit ``prompts`` together and step until drained. Returns the ids,
    every request's tokens, the order of the first tokens, and for each
    step its summary, its spans and the ids mid-prefill behind it."""
    rec, order, steps = get_recorder(), [], []

    def on_token(rid, _token, _last):
        if rid not in order:
            order.append(rid)

    ids = [engine.submit(p, max_length=NEW, on_token=on_token,
                         **({"seed": 100 + i, **how} if how else {}))
           for i, p in enumerate(prompts)]
    while _busy(engine):
        rec.clear()
        summary = engine.step()
        steps.append((summary, rec.spans(),
                      [r.id for r in engine._prefilling.values()]))
    results = engine.drain()
    return ids, [list(results[i].tokens) for i in ids], order, steps


def _alone(family, **how):
    """Every prompt through an engine of its own, one at a time (the same
    programs: ``sharing_programs``)."""
    out = []
    for i, prompt in enumerate(family.prompts):
        engine = family.engine()
        rid = engine.submit(prompt, max_length=NEW,
                            **({"seed": 100 + i, **how} if how else {}))
        out.append(list(engine.drain()[rid].tokens))
    return out


def _pools_clean(engine):
    manager = engine.cache_manager
    assert not engine._prefilling and manager.free_count == engine.slots
    assert manager.pages_in_use == 0
    manager.pool.check_invariants()
    if manager.window_pool is not None:
        manager.window_pool.check_invariants()


@pytest.fixture(scope="module")
def together(family):
    engine = family.engine()
    return (engine,) + _drive(engine, family.prompts)


# ------------------------------------------------------ what a step carries

def test_a_step_carries_two_chunks_of_the_prompt_mid_prefill(together):
    engine, ids, _, _, steps = together
    carried = [(s["admitted"], s["chunked"]) for s, _, _ in steps]
    # the first prompt: admitted with its first chunk and read on, two
    # chunks a step; the second is admitted behind its last part
    assert carried[:3] == [(1, 1), (0, 2), (1, 1)]
    assert [mid for _, _, mid in steps[:3]] == [ids[:1], ids[:1], ids[1:2]]
    for summary, spans, _ in steps:
        (tick,) = _named(spans, "serving.tick")
        assert tick.attrs["chunked"] == summary["chunked"]
    assert sum(chunked == 2 for _, chunked in carried) >= 3


def test_never_more_than_two_calls_a_step_nor_one_prompt_mid_prefill(
        together):
    engine, ids, _, _, steps = together
    calls = [len(_named(spans, "serving.prefill")) for _, spans, _ in steps]
    assert max(calls) == 2
    assert max(len(mid) for _, _, mid in steps) == 1
    for (summary, spans, _), n in zip(steps, calls):
        assert summary["chunked"] + summary["admitted"] == n <= 2
        assert len(_named(spans, "serving.prefill_chunk")) == n
    # the counter counts the steps that took the second call
    assert engine.metrics.snapshot()["second_chunks"] == calls.count(2) >= 6
    text = engine.metrics.registry.prometheus_text()
    assert (f'fleetx_serving_second_chunks_total{{engine="'
            f'{engine.metrics.engine_label}"}} {calls.count(2)}') in text
    # a prompt of four chunks and a part: five calls each, none twice
    assert sum(calls) == 5 * LANES
    _pools_clean(engine)


def test_every_token_is_the_one_of_a_request_served_alone(family, together):
    _, _, tokens, _, _ = together
    assert tokens == _alone(family)
    assert all(len(t) == NEW for t in tokens)


def test_sampled_tokens_are_those_of_a_request_served_alone(family):
    engine = family.engine()
    _, tokens, _, steps = _drive(engine, family.prompts, **SAMPLED)
    assert any(summary["chunked"] == 2 for summary, _, _ in steps)
    assert tokens == _alone(family, **SAMPLED)
    _pools_clean(engine)


def test_first_tokens_come_in_the_order_of_submission(together):
    _, ids, _, order, steps = together
    assert order == ids
    # and so do the admissions: a request leaves the queue behind every
    # older one, and only when the one before it has been read to its end
    admitted = [s.attrs["request"] for _, spans, _ in steps
                for s in _named(spans, "serving.admit")]
    assert admitted == ids


def test_two_short_prompts_are_admitted_in_one_step(plain):
    """Nothing mid-prefill: both passes admit, each its one call."""
    engine = plain.engine()
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 61, n, dtype=np.int32) for n in (5, 7, 3)]
    ids, tokens, order, steps = _drive(engine, prompts)
    assert [s["admitted"] for s, _, _ in steps[:2]] == [2, 1]
    assert order == ids
    for prompt, got in zip(prompts, tokens):
        alone = plain.engine()
        rid = alone.submit(prompt, max_length=NEW)
        assert got == list(alone.drain()[rid].tokens)
    _pools_clean(engine)


# --------------------------------------------------------------- crash safety

@pytest.mark.parametrize("attempt,mid,head", [
    (3, 3, 1), (5, None, 0)], ids=["second_chunk", "admission_behind"])
def test_a_fault_in_the_second_call_rolls_back_the_second_call_alone(
        family, together, attempt, mid, head):
    """Prefill attempts 0-4 are the first prompt's five calls, two a step,
    5 the second prompt's admission behind the last of them. A fault in a
    step's second call leaves the first call committed: the chunk before
    (``prefill_pos`` at three chunks), or the first prompt's last part
    (dispatched, its token unread: to the snapshot it is back at the
    queue's head, AHEAD of the request whose admission failed). Recovery
    then restarts what was mid-prefill and every token is the clean
    run's."""
    _, _, clean, _, _ = together
    engine = family.engine()
    seen, recover = {}, engine.recover

    def recovering():
        seen["mid"] = {r.id: r.prefill_pos
                       for r in engine._prefilling.values()}
        seen["queue"] = [(r.id, r.prefill_pos, r.slot, r.phase)
                         for r in engine.scheduler.snapshot()]
        return recover()

    engine.recover = recovering
    faults.configure(prefill_raise=str(attempt))
    ids, tokens, order, steps = _drive(engine, family.prompts)
    assert seen["mid"] == ({ids[0]: mid * family.chunk} if mid else {})
    assert seen["queue"] == [(i, 0, None, "queued") for i in ids[head:]]
    assert engine.metrics.engine_recoveries == 1
    assert engine.metrics.poison_retired == 0
    assert tokens == clean and order == ids
    _pools_clean(engine)


def test_recovery_keeps_arrival_order_behind_an_unread_first_token(
        family, together):
    """The step that runs the first prompt's last part admits the second
    behind it; its tick is the engine's first. A fault there finds the
    first prompt's token unread (the rollback puts it back at the queue's
    head) and the second prompt mid-prefill (recovery requeues it): the
    older one stays ahead."""
    _, _, clean, _, _ = together
    engine = family.engine()
    seen, recover = {}, engine.recover

    def recovering():
        seen["mid"] = [r.id for r in engine._prefilling.values()]
        seen["head"] = engine.scheduler.peek().id
        out = recover()
        seen["queue"] = [r.id for r in engine.scheduler.snapshot()]
        return out

    engine.recover = recovering
    faults.configure(tick_raise="0")
    ids, tokens, order, _ = _drive(engine, family.prompts)
    assert seen == {"mid": ids[1:2], "head": ids[0], "queue": ids}
    assert engine.metrics.engine_recoveries == 1
    assert tokens == clean and order == ids
    _pools_clean(engine)


# ------------------------------------------------------------------ deadlines

@pytest.mark.parametrize("limit", ["deadline_s", "queue_ttl_s"])
@pytest.mark.parametrize("when", ["between_steps", "between_chunks"])
def test_the_prompt_mid_prefill_expires_between_chunks(plain, limit, when):
    """The limits are checked before EVERY chunk: between two steps, and
    between the two chunks of one step. The call the expired prompt leaves
    goes to the next request."""
    clock = {"t": 0.0}
    engine = plain.engine()
    engine._now = lambda: clock["t"]
    ids = [engine.submit(p, max_length=NEW, **({limit: 5.0} if not i else {}))
           for i, p in enumerate(plain.prompts[:2])]
    engine.step()
    assert [(r.id, r.prefill_pos) for r in engine._prefilling.values()] == [
        (ids[0], 2 * plain.chunk)]
    if when == "between_steps":
        clock["t"] += 10.0
    else:
        run_chunk = engine._run_chunk

        def then_late(req):
            run_chunk(req)
            clock["t"] += 10.0

        engine._run_chunk = then_late
    summary = engine.step()
    engine._run_chunk = engine.__class__._run_chunk.__get__(engine)
    assert summary["timed_out"] == [ids[0]]
    if when == "between_steps":
        # the first pass finds it expired, the second admits the next
        assert (summary["admitted"], summary["chunked"]) == (1, 0)
    else:
        # its third chunk ran, its fourth did not
        assert (summary["admitted"], summary["chunked"]) == (0, 1)
    results = engine.drain()
    assert results[ids[0]].finish_reason == "timeout"
    assert not len(results[ids[0]].tokens)
    assert list(results[ids[1]].tokens) == _alone(plain)[1]
    _pools_clean(engine)


# ------------------------------------------ what an engine traces (PR 69)

@pytest.mark.parametrize("chunk,buckets", [(None, 5), (8, 2)],
                         ids=["whole_prompts", "chunked"])
def test_a_bucket_is_traced_once_and_two_chunks_add_no_program(
        plain, chunk, buckets):
    """The benchmark's warm-up (one request a bucket, each drained), then
    three requests together: an engine holds one prefill program a bucket,
    each traced ONCE, and the steps that carry two prefill-shaped calls
    trace, lower and compile nothing. (PR 69 made the prefill programs of
    the engines that never chunk dearer to trace; this pins the programs'
    count and that the second call is a call.)"""
    enable_compile_cache()       # the listeners of obs/compiles.py
    rec = get_recorder()
    rec.clear()
    kw = {**plain.kw, "prefill_chunk": chunk}
    engine = _engine.__wrapped__(plain.net, plain.variables, slots=LANES,
                                 **kw)
    rng = np.random.default_rng(3)
    for length in (4, 8, 12, 16, 20):
        engine.submit(rng.integers(1, 61, length, dtype=np.int32),
                      max_length=2)
        engine.drain()
    traced = [s.attrs["fun_name"] for s in _named(rec.spans(), "jit.trace")]
    assert traced.count("prefill") == len(engine._prefill_jits) == buckets
    assert traced.count("_decode_fn") == 1
    warm = engine.metrics.snapshot()["second_chunks"]
    _, tokens, _, steps = _drive(
        engine, [rng.integers(1, 61, 20, dtype=np.int32) for _ in range(3)])
    if chunk:
        two = sum(summary["chunked"] + summary["admitted"] == 2
                  for summary, _, _ in steps)
        assert two >= 3
    else:   # whole prompts: the parent's loop admits until a refusal
        assert steps[0][0]["admitted"] == 3
        two = 0
    assert engine.metrics.snapshot()["second_chunks"] - warm == two
    assert len(engine._prefill_jits) == buckets
    assert not [s for _, spans, _ in steps for s in spans
                if s.name.startswith("jit.")]
    assert all(len(t) == NEW for t in tokens)
