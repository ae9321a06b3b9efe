"""Engine-protocol conformance suite (docs/SERVING.md "Heterogeneous
fleet"): ONE parametrized battery over all three engine kinds — the
autoregressive GPT :class:`ServingEngine`, the encoder-style
:class:`ErnieScoringEngine`, and the KV-free :class:`EmbeddingEngine`.

The point of ``fleetx_tpu/serving/model_protocol.py`` is that the
router/API front doors consume ONLY the protocol surface, so every
behavior they rely on must hold for every engine kind, not just GPT:
bounded-queue admission (:class:`QueueFull`), queue-TTL and
total-deadline shedding to ``finish_reason="timeout"``, ``cancel()``,
drain-mode rejection (:class:`ShuttingDown`) with terminal results for
everything in flight, the ``/healthz`` report shape (model family +
capability flags included — what model-aware routing groups on), and
the metrics snapshot shape. A new engine that passes this file can be
dropped into a heterogeneous fleet unchanged."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fleetx_tpu.models.ernie.model import ErnieConfig, ErnieForPretraining
from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.models.vision.vit import ViT, ViTConfig
from fleetx_tpu.serving import (
    ENGINE_SURFACE,
    EmbeddingEngine,
    ErnieScoringEngine,
    QueueFull,
    ServingEngine,
    ShuttingDown,
    encode_floats,
    engine_conforms,
)

GEN = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                       pad_token_id=60, max_length=4)


@pytest.fixture(scope="module")
def zoo():
    """One tiny model per family, initialized once for the module."""
    gcfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    gpt = GPTForPretraining(gcfg)
    gpt_vars = jax.jit(gpt.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))

    ecfg = ErnieConfig(
        vocab_size=97, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32)
    ernie = ErnieForPretraining(ecfg)
    ernie_vars = jax.jit(ernie.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]

    vcfg = ViTConfig(image_size=8, patch_size=4, in_channels=3,
                     num_classes=0, hidden_size=32, num_layers=1,
                     num_attention_heads=2, drop_rate=0.0,
                     attn_drop_rate=0.0, dtype=jnp.float32,
                     use_flash_attention=False)
    vit = ViT(vcfg)
    vit_vars = jax.jit(vit.init)(jax.random.PRNGKey(1),
                                 np.zeros((1, 8, 8, 3), np.float32))
    return {"gpt": (gpt, gpt_vars), "ernie": (ernie, ernie_vars),
            "vit": (vit, vit_vars)}


def _make(zoo, kind, **kw):
    """Build a fresh engine of ``kind`` honoring the shared knobs the
    protocol tests exercise (slots / max_queue)."""
    model, variables = zoo[kind]
    if kind == "gpt":
        return ServingEngine(model, variables,
                             slots=kw.pop("slots", 2),
                             cache_len=32, gen_cfg=GEN,
                             prefill_bucket=4, **kw)
    if kind == "ernie":
        return ErnieScoringEngine(model, {"params": variables}
                                  if "params" not in variables
                                  else variables,
                                  slots=kw.pop("slots", 2), **kw)
    return EmbeddingEngine(model, variables, slots=kw.pop("slots", 2), **kw)


def _prompt(kind, salt=0):
    """A valid request payload per family (the wire is int32 either
    way — tokens for text, bit-cast image floats for vision)."""
    if kind == "gpt":
        return np.asarray([1 + salt, 2, 3], np.int32)
    if kind == "ernie":
        # fill-in-blank shape: one mask token (default mask id 3)
        return np.asarray([5 + salt, 3, 9, 11], np.int32)
    rng = np.random.RandomState(7 + salt)
    return encode_floats(rng.rand(8, 8, 3).astype(np.float32))


KINDS = ("gpt", "ernie", "vit")


@pytest.fixture(params=KINDS)
def kind(request):
    return request.param


# ------------------------------------------------------- surface shape


def test_surface_conforms(zoo, kind):
    """engine_conforms (the router's ctor gate) passes, and every
    ENGINE_SURFACE method is a real callable."""
    eng = _make(zoo, kind)
    assert engine_conforms(eng, require_attrs=True) is None
    for name in ENGINE_SURFACE:
        assert callable(getattr(eng, name)), name


def test_health_report_shape(zoo, kind):
    """/healthz body: drain-aware state plus the model family and
    capability flags model-aware routing groups replicas by."""
    eng = _make(zoo, kind)
    h = eng.health()
    for key in ("state", "role", "model", "capabilities", "queue_depth",
                "queue_tokens", "active", "slots"):
        assert key in h, (kind, key, sorted(h))
    assert h["state"] == "ok"
    caps = h["capabilities"]
    assert caps["family"] == h["model"]
    assert caps["emits"] in ("tokens", "floats")
    assert isinstance(caps["has_kv_cache"], bool)
    if kind == "gpt":
        assert caps["has_kv_cache"] and h["model"] == "gpt"
        assert caps["cache_layout"] == "paged" and eng.paged is True
    else:
        assert not caps["has_kv_cache"] and caps["cache_layout"] == "none"
        assert eng.paged is False
    eng.request_shutdown()
    assert eng.health()["state"] == "draining"
    eng.drain()
    eng2 = _make(zoo, kind)
    eng2.declare_dead()
    assert eng2.health()["state"] == "dead"


def test_submit_limit_is_the_rejection_bound(zoo, kind):
    """submit_limit is the smallest rejected per-request input size —
    the number the router prices admission with."""
    eng = _make(zoo, kind)
    lim = eng.submit_limit
    assert isinstance(lim, int) and lim > 1
    with pytest.raises(ValueError):
        eng.submit(np.ones(lim, np.int32))


# --------------------------------------------------- admission + sheds


def test_bounded_queue_rejects(zoo, kind):
    """Past max_queue, submit raises QueueFull and the reject is
    counted — backpressure, never silent loss."""
    eng = _make(zoo, kind, max_queue=1)
    eng.submit(_prompt(kind))
    with pytest.raises(QueueFull):
        eng.submit(_prompt(kind, salt=1))
    assert eng.metrics.snapshot()["rejected"] >= 1
    eng.drain()


def test_queue_ttl_sheds_to_timeout(zoo, kind):
    """A request whose queue-TTL lapses before admission retires as
    finish_reason="timeout" with no tokens; its neighbor finishes."""
    eng = _make(zoo, kind, slots=1)
    clock = {"t": 0.0}
    eng._now = lambda: clock["t"]
    ra = eng.submit(_prompt(kind))
    eng.step()  # ra admitted (and, for the KV-free engines, finished)
    rb = eng.submit(_prompt(kind, salt=1), queue_ttl_s=1.0)
    clock["t"] += 5.0
    eng.step()
    res = eng.drain()
    assert res[rb].finish_reason == "timeout" and not len(res[rb].tokens)
    assert res[ra].finish_reason in ("max_length", "complete")
    assert len(res[ra].tokens) > 0


def test_deadline_sheds_to_timeout(zoo, kind):
    """A total-deadline lapse sheds the request as timeout even if it
    never reached a slot."""
    eng = _make(zoo, kind)
    clock = {"t": 0.0}
    eng._now = lambda: clock["t"]
    rid = eng.submit(_prompt(kind), deadline_s=1.0)
    clock["t"] += 5.0
    eng.step()
    res = eng.drain()
    assert res[rid].finish_reason == "timeout", res[rid]


def test_cancel_is_terminal_and_idempotent(zoo, kind):
    """cancel() yields exactly one "cancelled" result; cancelling a
    finished request returns False and changes nothing."""
    eng = _make(zoo, kind)
    ra = eng.submit(_prompt(kind))
    rb = eng.submit(_prompt(kind, salt=1))
    assert eng.cancel(rb) is True
    assert eng.cancel(rb) is False
    res = eng.drain()
    assert res[rb].finish_reason == "cancelled" and not len(res[rb].tokens)
    assert res[ra].finish_reason in ("max_length", "complete")
    assert eng.cancel(ra) is False


def test_drain_rejects_new_and_terminates_inflight(zoo, kind):
    """request_shutdown(): new submits raise ShuttingDown; drain()
    returns a terminal result for EVERYTHING already accepted."""
    eng = _make(zoo, kind)
    rids = [eng.submit(_prompt(kind, salt=i)) for i in range(3)]
    eng.request_shutdown()
    with pytest.raises(ShuttingDown):
        eng.submit(_prompt(kind, salt=9))
    res = eng.drain()
    terminal = ("max_length", "complete", "shutdown", "timeout")
    for rid in rids:
        assert rid in res and res[rid].finish_reason in terminal, res.get(rid)


# ------------------------------------------------------------- metrics


def test_metrics_snapshot_shape(zoo, kind):
    """The ServingMetrics snapshot keys dashboards key on hold for
    every engine kind (one obs story across the fleet)."""
    eng = _make(zoo, kind)
    rids = [eng.submit(_prompt(kind, salt=i)) for i in range(2)]
    res = eng.drain()
    assert all(len(res[r].tokens) > 0 for r in rids)
    m = eng.metrics.snapshot()
    for key in ("submitted", "admitted", "retired", "rejected", "timeouts",
                "cancels", "tokens_generated", "ticks", "queue_depth",
                "slots", "ttft_ms_p50"):
        assert key in m, (kind, key)
    assert m["submitted"] == m["admitted"] == m["retired"] == 2
    assert m["tokens_generated"] > 0 and m["queue_depth"] == 0


def test_results_are_exact_and_deterministic(zoo, kind):
    """Same request twice → byte-identical wire tokens (the invariant
    router migration and the chaos suites lean on)."""
    eng = _make(zoo, kind)
    r1 = eng.submit(_prompt(kind))
    r2 = eng.submit(_prompt(kind))
    res = eng.drain()
    assert np.array_equal(res[r1].tokens, res[r2].tokens)
    eng2 = _make(zoo, kind)
    r3 = eng2.submit(_prompt(kind))
    res2 = eng2.drain()
    assert np.array_equal(res2[r3].tokens, res[r1].tokens)


@pytest.mark.parametrize("others", [1, 3], ids=["1", "slots-1"])
@pytest.mark.parametrize("kind", ["vit", "ernie"])
def test_result_does_not_depend_on_batch_occupancy(zoo, kind, others):
    """A request's bytes are the same alone in its batch and beside
    ``others`` different requests: one program shape per engine (a
    second batch shape is a second program, which XLA may round
    differently in the last bits)."""
    slots = 4
    alone = _make(zoo, kind, slots=slots)
    rid = alone.submit(_prompt(kind))
    want = alone.drain()[rid].tokens
    eng = _make(zoo, kind, slots=slots)
    rid = eng.submit(_prompt(kind))
    for salt in range(1, others + 1):
        eng.submit(_prompt(kind, salt=salt))
    tick = eng.step()
    assert tick["admitted"] == 1 + others and tick["forwards"] == 1
    got = eng.drain()
    assert np.array_equal(got[rid].tokens, want)


# --------------------------------------------- the executor's forward seam


def _forward_operands(eng, rows=8, batch=2):
    """A cached forward of ``batch`` sequences of ``rows`` tokens through
    the engine's own pool: each sequence in pages of its own."""
    pages = eng.cache_len // eng.page_size  # a lane's whole table row
    tables = 1 + np.arange(batch * pages, dtype=np.int32).reshape(batch, -1)
    ids = np.random.RandomState(5).randint(1, 61, (batch, rows))
    pos = np.broadcast_to(np.arange(rows, dtype=np.int32), (batch, rows))
    return (eng.params, eng.cache_manager.cache, jnp.asarray(ids, jnp.int32),
            jnp.asarray(pos)), dict(
        cache_positions=jnp.zeros((batch,), jnp.int32),
        block_tables=jnp.asarray(tables))


def test_forward_returns_every_row_by_default(zoo):
    """The tick's and the verify call's contract: ``forward`` without
    ``logit_rows`` is ``decode_step`` (one ``model.apply``, body and
    head), every row's logits, bit for bit."""
    from fleetx_tpu.models.gpt.generation import decode_step

    eng = _make(zoo, "gpt")
    args, kw = _forward_operands(eng)
    logits, cache = jax.jit(
        lambda *a: eng.executor.forward(*a, **kw))(*args)
    want, want_cache = jax.jit(lambda params, cache, ids, pos: decode_step(
        eng.executor.model, params, cache, ids, pos, **kw))(*args)
    assert logits.shape == (2, 8, 61) and logits.dtype == jnp.float32
    np.testing.assert_array_equal(logits, want)
    jax.tree.map(np.testing.assert_array_equal, cache, want_cache)


@pytest.mark.parametrize("rows", [(7, 2), (0, -1), (-1, -1)],
                         ids=["a_row_each", "one_wants_none", "none_wanted"])
def test_forward_applies_the_head_to_the_rows_asked_for(zoo, rows):
    """One row a sequence: ``[b, 1, vocab]``, the logits the default gives
    for that row (the product of one row where the default's is of all, so
    to float32 rounding), and the same cache whatever was asked for. A
    sequence that asks for none has no logits to read; where none does the
    head is not run and zeros come back."""
    eng = _make(zoo, "gpt")
    args, kw = _forward_operands(eng)
    every, want_cache = jax.jit(
        lambda *a: eng.executor.forward(*a, **kw))(*args)
    logits, cache = jax.jit(lambda *a: eng.executor.forward(
        *a, logit_rows=jnp.asarray(rows, jnp.int32), **kw))(*args)
    assert logits.shape == (2, 1, 61) and logits.dtype == jnp.float32
    jax.tree.map(np.testing.assert_array_equal, cache, want_cache)
    for b, row in enumerate(rows):
        if row >= 0:
            np.testing.assert_allclose(logits[b, 0], every[b, row],
                                       rtol=1e-5, atol=1e-6)
    if max(rows) < 0:
        assert not np.asarray(logits).any()
