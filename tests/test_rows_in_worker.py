"""A prompt's images are keyed and cut off the step loop's thread
(``serving/rows_in.py`` "On which thread"): ``submit`` derives what the ids
and the images' shapes say and raises what it always raised, the tower's one
worker thread hashes the images (the request is admissible once its keys are
whole) and then cuts them, and the step loop takes an image's patches when it
is about to encode it. Held here, on the CPU at the tiny size of
``tests/test_keyevl2_serving.py``: the layout against the tree's before the
worker, bit for bit; a head of the queue without keys blocking admission and
nothing else; every refusal still ``submit``'s; the counters; and no thread
left behind by a rollback, an expiry, a shutdown."""

import hashlib
import threading
import time

import numpy as np
import pytest

from serving_parity import sharing_programs
from test_keyevl2_serving import SIZES, TOKEN, session, tiny_model

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.vision import vit
from fleetx_tpu.models.vision.vit import image_patches
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.serving import ServingEngine, rows_in

GROUP = SIZES["vision"]
WAIT_S = 60.0


def layout_before(prompt, images, group):
    """``rows_in.layout`` as it stood before the worker (the commit before
    this file): every byte copied, hashed and cut in one loop."""
    patch, merge, token = (group["patch_size"], group["merge"],
                           group["image_token_id"])
    marked = prompt == token
    keys = prompt.astype(np.int64)
    positions = np.empty((3, len(prompt)), np.int32)
    edges = np.flatnonzero(np.diff(np.concatenate(
        [[False], marked, [False]]).astype(np.int8)))
    records, n, at, taken = [], 0, 0, 0
    for begin, end in zip(edges[::2], edges[1::2]):
        positions[:, at:begin] = n + np.arange(begin - at, dtype=np.int32)
        n += begin - at
        at = begin
        while at < end:
            image = np.asarray(images[taken])
            pixels = image_patches(image, patch, merge)
            h, w = (image.shape[0] // (patch * merge),
                    image.shape[1] // (patch * merge))
            r, c = np.divmod(np.arange(h * w, dtype=np.int32), w)
            positions[:, at:at + h * w] = n + np.stack(
                [np.zeros_like(r), r, c])
            digest = hashlib.blake2b(
                np.ascontiguousarray(image).tobytes()
                + np.asarray(image.shape, np.int64).tobytes(),
                digest_size=8).digest()
            place = (np.arange(h * w, dtype=np.uint64)
                     * np.uint64(0x9E3779B97F4A7C15))
            mixed = ((np.uint64(int.from_bytes(digest, "little")) + place)
                     & np.uint64((1 << 62) - 1))
            keys[at:at + h * w] = -1 - mixed.astype(np.int64)
            records.append({"start": int(at), "grid": (h * merge, w * merge),
                            "patches": pixels})
            n += max(h, w)
            at += h * w
            taken += 1
    positions[:, at:] = n + np.arange(len(prompt) - at, dtype=np.int32)
    n += len(prompt) - at
    return keys, positions, int(n - len(prompt)), records


class Inline:
    """A worker that runs what it is handed at once, on the caller's
    thread: the engine as it was before it had one."""

    def submit(self, fn, *args):
        future = rows_in.Future()
        future.set_result(fn(*args))
        return future


@sharing_programs
def _engine(model, variables, **kw):
    return ServingEngine(
        model, variables, slots=3, cache_len=512, page_size=8,
        num_pages=3 * 64 + 1, prefill_chunk=32, prefill_bucket=16,
        prefix_cache=True, gen_cfg=GenerationConfig(
            decode_strategy="greedy", eos_token_id=-1, pad_token_id=0,
            max_length=8), **kw)


@pytest.fixture(scope="module")
def built():
    return tiny_model()


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture
def engine(built):
    """A fresh engine (the file's programs compiled once), and afterwards
    no worker thread of it alive."""
    made = _engine(built[0], built[1])
    yield made
    assert made._tower.worker.join(WAIT_S)
    assert not _threads(made)


def _threads(engine):
    return [t for t in threading.enumerate()
            if t.name == engine._tower.worker._name]


@pytest.fixture
def held(monkeypatch):
    """A worker thread held at its first image until the event is set
    (:class:`Inline`, on the caller's thread, is not)."""
    release, real = threading.Event(), rows_in.image_digest

    def waits(image):
        if threading.current_thread().name.startswith("fleetx-rows-in"):
            assert release.wait(timeout=WAIT_S)
        return real(image)

    monkeypatch.setattr(rows_in, "image_digest", waits)
    yield release
    release.set()


def _prompt(seed, grids, caption=4, tail=12):
    tokens, images = session(seed, grids=grids, caption=caption, tail=tail)
    return tokens[:-6], images


def _adjacent(seed):
    """Two images in ONE run of ``image_token_id``, of both patch buckets
    (17 x 16 rows are 1,088 patches), then a third after text."""
    rng = np.random.default_rng(seed)
    grids = ((2, 2), (17, 16), (3, 2))
    images = [rng.integers(0, 256, (h * 4, w * 4, 3), dtype=np.uint8)
              for h, w in grids]
    text = lambda n: rng.integers(1, 500, n, dtype=np.int32)  # noqa: E731
    return np.concatenate([
        text(5), np.full(2 * 2 + 17 * 16, TOKEN, np.int32), text(3),
        np.full(3 * 2, TOKEN, np.int32), text(9)]), images


def _edges(seed):
    """A prompt that begins and ends with an image's rows."""
    rng = np.random.default_rng(seed)
    images = [rng.integers(0, 256, (h * 4, w * 4, 3), dtype=np.uint8)
              for h, w in ((2, 2), (1, 5))]
    return np.concatenate([
        np.full(4, TOKEN, np.int32), rng.integers(1, 500, 3, dtype=np.int32),
        np.full(5, TOKEN, np.int32)]), images


CASES = {
    "one_image": lambda: _prompt(11, ((3, 2),)),
    "several": lambda: _prompt(12, ((2, 3), (4, 2), (3, 3))),
    "adjacent_both_buckets": lambda: _adjacent(13),
    "large_bucket_alone": lambda: _prompt(14, ((16, 17),)),
    "image_first_and_last": lambda: _edges(15),
}


def _same(mine, want):
    keys, positions, delta, records = want
    assert np.array_equal(mine[0], keys) and mine[0].dtype == keys.dtype
    assert np.array_equal(mine[1], positions)
    assert mine[1].dtype == positions.dtype and mine[2] == delta
    assert len(mine[3]) == len(records)
    for got, record in zip(mine[3], records):
        assert (got["start"], got["grid"]) == (record["start"],
                                               record["grid"])
        cut = got["patches"]
        cut = cut.result(timeout=WAIT_S) if hasattr(cut, "result") else cut
        assert np.array_equal(cut, record["patches"])
        assert cut.dtype == record["patches"].dtype


# ------------------------------------------------ (a) the same layout

@pytest.mark.parametrize("case", sorted(CASES))
def test_the_layout_is_the_one_before_the_worker_bit_for_bit(case, engine):
    prompt, images = CASES[case]()
    want = layout_before(prompt, images, GROUP)
    assert (want[0] < 0).sum() == (prompt == TOKEN).sum()
    _same(rows_in.layout(prompt, images, GROUP), want)
    # and through submit: the outline at once, the bytes' part when the
    # worker has run
    rid = engine.submit(prompt, images=images)
    req = engine.scheduler.peek()
    assert req.id == rid and req.keyed is not None
    assert np.array_equal(req.positions, want[1])
    assert req.rope_delta == want[2]
    req.keyed.result(timeout=WAIT_S)
    _same((req.keys, req.positions, req.rope_delta, req.images), want)
    assert engine.cancel(rid)


def test_a_prompt_of_ids_alone_starts_no_thread(engine):
    rid = engine.submit(np.arange(1, 30, dtype=np.int32), max_length=2)
    assert engine.scheduler.peek().keyed is None
    assert len(engine.drain()[rid].tokens) == 2
    assert engine._tower.worker._thread is None
    snap = engine.metrics.snapshot()
    assert snap["layout_blocked_steps"] == 0 and snap["images_cut"] == 0
    assert snap["layout_ms_p50"] is None


def test_an_engine_without_a_tower_has_no_worker():
    import jax

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    before = set(threading.enumerate())
    model = GPTForPretraining(GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype="float32", use_flash_attention=False))
    eng = ServingEngine(
        model, jax.jit(model.init)(jax.random.PRNGKey(0),
                                   np.zeros((2, 8), np.int32)),
        slots=2, cache_len=32, page_size=8, prefill_bucket=4)
    assert eng._tower is None
    with pytest.raises(ValueError, match="takes no images"):
        eng.submit(np.arange(1, 9, dtype=np.int32),
                   images=[np.zeros((8, 8, 3), np.uint8)])
    rid = eng.submit(np.arange(1, 20, dtype=np.int32), max_length=2)
    assert eng.scheduler.peek().keyed is None
    assert len(eng.drain()[rid].tokens) == 2
    assert set(threading.enumerate()) <= before


# -------------------------------- (b) a head without keys blocks, only that

def _run(engine, submits):
    """``submits`` ((prompt, images, max_length) each) through ``engine``
    in order; every request's tokens."""
    ids = [engine.submit(p, images=i, max_length=n) for p, i, n in submits]
    done = engine.drain()
    return [list(done[i].tokens) for i in ids]


def test_a_head_without_keys_blocks_admission_and_not_the_ticks(
        built, engine, held):
    first = (np.arange(3, 40, dtype=np.int32), None, 40)
    with_images = (*_prompt(21, ((2, 3), (3, 3))), 5)
    behind = (np.arange(7, 33, dtype=np.int32), None, 4)
    # the engine that lays out inside submit, as before the worker
    plain = _engine(built[0], built[1])
    plain._tower.worker = Inline()
    want = _run(plain, [first, with_images, behind])

    streamed = []
    running = engine.submit(first[0], max_length=first[2],
                            on_token=lambda rid, tok, end: streamed.append(tok))
    while len(streamed) < 3:
        engine.step()
    a = engine.submit(with_images[0], images=with_images[1],
                      max_length=with_images[2])
    b = engine.submit(behind[0], max_length=behind[2])
    queued = {r.id: r for r in engine.scheduler.snapshot()}
    assert sorted(queued) == [a, b] and not queued[a].keyed.done()
    had = len(streamed)
    for _ in range(6):
        summary = engine.step()
        # nothing admitted, neither the head nor the one behind it
        assert summary["admitted"] == 0 and summary["queue_depth"] == 2
    # and the lane that was decoding has decoded, a token a step
    assert len(streamed) == had + 6
    snap = engine.metrics.snapshot()
    assert snap["layout_blocked_steps"] == 6
    assert snap["images_cut"] == 0 and snap["layout_ms_p50"] is None
    held.set()
    queued[a].keyed.result(timeout=WAIT_S)
    done = engine.drain()
    assert queued[a].admit_time <= queued[b].admit_time   # arrival order
    assert [list(done[i].tokens) for i in (running, a, b)] == want
    snap = engine.metrics.snapshot()
    assert snap["images_cut"] == snap["images_encoded"] == 2
    assert snap["layout_ms_p50"] > 0


def test_an_idle_engine_waits_for_the_worker_and_does_not_spin(engine,
                                                               monkeypatch):
    prompt, images = _prompt(22, ((4, 4), (3, 5)))
    real = rows_in.image_digest

    def keys(image):
        time.sleep(0.2)
        return real(image)

    monkeypatch.setattr(rows_in, "image_digest", keys)
    t0 = time.perf_counter()
    rid = engine.submit(prompt, images=images, max_length=2)
    req, steps = engine.scheduler.peek(), 0
    while not req.keyed.done():
        engine.step()
        steps += 1
    waited = time.perf_counter() - t0
    assert len(engine.drain()[rid].tokens) == 2
    # 0.4 s of hashing at 0.05 s a waiting step, not thousands of steps
    assert waited >= 0.4 and 1 <= steps <= waited / rows_in._IDLE_WAIT_S + 1
    # (the step in which the wait ended admitted it)
    assert engine.metrics.snapshot()["layout_blocked_steps"] in (steps - 1,
                                                                  steps)


# ------------------------------------------- (c) every refusal is submit's

def _refusals():
    prompt, images = _prompt(31, ((2, 3), (4, 2), (3, 3)))
    odd = np.zeros((10, 12, 3), np.uint8)           # 10 is no whole row
    huge = np.zeros((33 * 4, 32 * 4, 3), np.uint8)  # 4,224 patches
    return {
        "no_image_left": (prompt, images[:2], "no image is left"),
        "no_image_at_all": (prompt, None, "no image is left"),
        "images_left_over": (prompt[:14], images, "3 images given"),
        "run_too_short": (prompt, [images[1]] + images[1:],
                          "rows and the run"),
        "dtype": (prompt, [i.astype(np.float32) for i in images], "uint8"),
        "ndim": (prompt, [i[..., 0] for i in images], "uint8"),
        "no_whole_rows": (prompt, [odd] + images[1:], "no whole number"),
        "too_many_patches": (np.concatenate(
            [prompt[:6], np.full(33 * 32, TOKEN, np.int32)]), [huge],
            "at most 4096"),
        "no_decode_room": (np.concatenate(
            [prompt, np.arange(1, 500, dtype=np.int32)]), images,
            "leaves no decode room"),
    }


@pytest.mark.parametrize("case", sorted(_refusals()))
def test_every_refusal_is_raised_in_submit_and_leaves_nothing(case, engine):
    prompt, images, message = _refusals()[case]
    handed = []
    engine._tower.worker.submit = lambda *a: handed.append(a)
    with pytest.raises(ValueError, match=message):
        engine.submit(prompt, images=images)
    assert engine.scheduler.queue_depth == 0 and not handed
    assert engine.metrics.snapshot()["submitted"] == 0


def test_the_refusals_are_the_ones_before_the_worker():
    for case, (prompt, images, message) in _refusals().items():
        if case == "no_decode_room":        # (the engine's own, not layout's)
            continue
        with pytest.raises(ValueError, match=message):
            rows_in.layout(prompt, images, GROUP)
        with pytest.raises(ValueError, match=message):
            rows_in.outline(prompt, images, GROUP)


def test_the_bytes_are_read_on_the_worker_alone(engine, monkeypatch):
    """The two readers of an image's bytes (its digest, its cut) run on
    the worker's thread, never on the one that calls ``submit`` and
    ``step``."""
    readers = []
    keys, cut = rows_in.image_digest, vit.image_patches
    monkeypatch.setattr(rows_in, "image_digest", lambda *a: (
        readers.append(("keys", threading.current_thread().name)),
        keys(*a))[1])
    monkeypatch.setattr(vit, "image_patches", lambda *a: (
        readers.append(("cut", threading.current_thread().name)),
        cut(*a))[1])
    prompt, images = _prompt(32, ((2, 3), (3, 3)))
    rid = engine.submit(prompt, images=images, max_length=2)
    assert len(engine.drain()[rid].tokens) == 2
    name = engine._tower.worker._name
    assert readers == [("keys", name)] * 2 + [("cut", name)] * 2


# ------------------------------------------------------- (d) the counters

def test_a_second_question_cuts_no_image_the_match_spared(engine):
    tokens, images = session(41)
    n = len(tokens)
    rid = engine.submit(tokens[:n - 6], images=images, max_length=3)
    cold = engine.drain()[rid].tokens
    first = engine.metrics.snapshot()
    assert first["images_cut"] == first["images_encoded"] == 3
    assert first["images_skipped"] == 0
    other = tokens[:n - 6].copy()
    other[-8:] = np.random.default_rng(42).integers(1, 500, 8)
    waited = []
    real = rows_in.Future.result

    def result(self, timeout=None):
        waited.append(self)
        return real(self, timeout)

    rows_in.Future.result = result
    try:
        rid = engine.submit(other, images=images, max_length=3)
        req = engine.scheduler.peek()
        hit = engine.drain()[rid].tokens
    finally:
        rows_in.Future.result = real
    second = engine.metrics.snapshot()
    assert second["images_skipped"] == 3
    assert second["images_cut"] == second["images_encoded"] == 3
    # no image of it was waited for, though the worker has cut each
    assert not [r for r in req.images if r["patches"] in waited]
    assert all(r["patches"].result(timeout=WAIT_S).shape[1] == 12
               for r in req.images)
    assert len(hit) == len(cold) == 3
    # a session the trie holds in part: the images past the match are cut
    longer, more = session(41, grids=((2, 3), (4, 2), (3, 3), (2, 2)))
    assert np.array_equal(longer[:30], tokens[:30])
    rid = engine.submit(longer[:-6], images=more, max_length=2)
    engine.drain()
    third = engine.metrics.snapshot()
    assert third["images_cut"] == third["images_encoded"]
    assert (third["images_cut"] - second["images_cut"]
            + third["images_skipped"] - second["images_skipped"]) == 4
    assert third["layout_ms_max"] >= third["layout_ms_p50"] > 0


# ------------------------------------------ (e) nothing is left behind

def _pending(engine, seed, **kw):
    prompt, images = _prompt(seed, ((2, 3), (3, 3)))
    rid = engine.submit(prompt, images=images, max_length=4, **kw)
    req = next(r for r in engine.scheduler.snapshot() if r.id == rid)
    assert not req.keyed.done()
    return rid, req, (prompt, images, 4)


def test_a_step_that_rolls_back_keeps_the_pending_request_queued(
        built, engine, held):
    text = (np.arange(5, 41, dtype=np.int32), None, 12)
    running = engine.submit(text[0], max_length=text[2])
    for _ in range(3):
        engine.step()
    rid, req, asked = _pending(engine, 51)
    faults.configure(tick_raise=str(engine._fault_ticks))
    try:
        summary = engine.step()
    finally:
        faults.reset()
    assert summary["recovered"] and engine.metrics.engine_recoveries == 1
    assert [r.id for r in engine.scheduler.snapshot()] == [rid]
    assert not req.keyed.done() and req.phase == "queued"
    held.set()
    done = engine.drain()
    plain = _engine(built[0], built[1])
    plain._tower.worker = Inline()
    assert [list(done[running].tokens), list(done[rid].tokens)] == _run(
        plain, [text, asked])
    assert done[rid].finish_reason == "max_length"


def test_a_request_that_expires_in_the_queue_unkeyed_times_out(engine, held):
    rid, req, _ = _pending(engine, 52, queue_ttl_s=0.01)
    time.sleep(0.05)
    summary = engine.step()
    assert summary["timed_out"] == [rid] and summary["queue_depth"] == 0
    result = engine.drain()[rid]
    assert result.finish_reason == "timeout" and len(result.tokens) == 0
    held.set()
    req.keyed.result(timeout=WAIT_S)          # the worker ends its work


def test_a_cancel_of_a_request_without_keys(engine, held):
    rid, req, _ = _pending(engine, 53)
    assert engine.cancel(rid) and engine.scheduler.queue_depth == 0
    assert engine.drain()[rid].finish_reason == "cancelled"


def test_a_shutdown_whose_grace_is_over_retires_it_unkeyed(engine, held):
    rid, req, _ = _pending(engine, 54)
    done = engine.shutdown(grace_s=0.0)      # (scheduler.drain_all)
    assert done[rid].finish_reason == "shutdown"
    assert len(done[rid].tokens) == 0 and not len(engine.scheduler)
    with pytest.raises(Exception, match="draining"):
        engine.submit(np.arange(1, 9, dtype=np.int32))


def test_a_shutdown_inside_its_grace_serves_it_once_keyed(built, engine,
                                                          held):
    rid, req, asked = _pending(engine, 55)
    timer = threading.Timer(0.3, held.set)
    timer.start()
    try:
        done = engine.shutdown(grace_s=WAIT_S)
    finally:
        timer.cancel()
    plain = _engine(built[0], built[1])
    plain._tower.worker = Inline()
    assert [list(done[rid].tokens)] == _run(plain, [asked])
    assert done[rid].finish_reason == "max_length"
    assert engine.metrics.snapshot()["layout_blocked_steps"] <= 40


def test_images_the_worker_cannot_read_end_the_request_alone(engine,
                                                             monkeypatch):
    def broken(image):
        raise MemoryError("no room for the image")

    prompt, images = _prompt(56, ((2, 3),))
    monkeypatch.setattr(rows_in, "image_digest", broken)
    bad = engine.submit(prompt, images=images, max_length=2)
    good = engine.submit(np.arange(1, 20, dtype=np.int32), max_length=2)
    done = engine.drain()
    assert done[bad].finish_reason == "error" and not len(done[bad].tokens)
    assert len(done[good].tokens) == 2


def test_the_worker_thread_lives_only_while_it_has_work():
    worker = rows_in._Worker("fleetx-rows-in-test")
    gate, order = threading.Event(), []

    def job(n):
        assert gate.wait(timeout=WAIT_S)
        order.append(n)
        return n

    futures = [worker.submit(job, n) for n in range(4)]
    thread = worker._thread
    assert thread.is_alive() and thread.daemon and not worker.join(0.01)
    gate.set()
    assert [f.result(timeout=WAIT_S) for f in futures] == order == [0, 1, 2, 3]
    assert worker.join(WAIT_S) and not thread.is_alive()
    # an exception is the future's, and the next job still runs
    failed = worker.submit(lambda: 1 // 0)
    after = worker.submit(job, 9)
    with pytest.raises(ZeroDivisionError):
        failed.result(timeout=WAIT_S)
    assert after.result(timeout=WAIT_S) == 9 and worker.join(WAIT_S)
    # a job cancelled before its turn is passed over, and the next runs
    gate.clear()
    first, dropped, last = (worker.submit(job, n) for n in (5, 6, 7))
    assert dropped.cancel()
    gate.set()
    assert (first.result(timeout=WAIT_S), last.result(timeout=WAIT_S)) == (5, 7)
    assert worker.join(WAIT_S) and 6 not in order
