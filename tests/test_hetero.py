"""Heterogeneous-fleet suite (docs/SERVING.md "Heterogeneous fleet"):
model-aware routing units plus the API surface over a mixed fleet.

The router half proves dispatch is MODEL-AWARE: ``submit(model=...)``
lands only on that family's replica group (asserted on every prompt
each engine ever saw), an unknown family is a clean submit-time
``ValueError`` (never an enqueued request), failover after a replica
death stays INSIDE the group, and a fully-dead group strands only its
own requests while the other families keep serving. The API half
proves ``/v1/models`` derives from the router's replica groups and
``/v1/embeddings`` fronts the KV-free embedding family end-to-end —
float vectors in, float vectors out, bit-identical to the engine's
int32 wire tokens."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fleetx_tpu.models.ernie.model import ErnieConfig, ErnieForPretraining
from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.models.vision.vit import ViT, ViTConfig
from fleetx_tpu.obs import get_event_log
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.serving import (
    EmbeddingEngine,
    ErnieScoringEngine,
    ServingEngine,
    ServingRouter,
    decode_floats,
    encode_floats,
)
from fleetx_tpu.serving.api.server import ApiServer

pytestmark = pytest.mark.chaos

GEN = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                       pad_token_id=60, max_length=8)

GPT_PROMPTS = [np.asarray([1, 2, 3], np.int32),
               np.asarray([4, 5, 6, 7, 8], np.int32)]


@pytest.fixture(scope="module")
def zoo():
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
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))

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


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    get_event_log().clear()
    yield
    faults.reset()


def _gpt(zoo, **kw):
    model, variables = zoo["gpt"]
    return ServingEngine(model, variables, slots=kw.pop("slots", 2),
                         cache_len=32, gen_cfg=GEN, prefill_bucket=4, **kw)


def _ernie(zoo, **kw):
    model, variables = zoo["ernie"]
    return ErnieScoringEngine(model, variables, slots=kw.pop("slots", 2),
                              **kw)


def _vit(zoo, **kw):
    model, variables = zoo["vit"]
    return EmbeddingEngine(model, variables, slots=kw.pop("slots", 2), **kw)


def _image(salt=0):
    rng = np.random.RandomState(7 + salt)
    return rng.rand(8, 8, 3).astype(np.float32)


# ------------------------------------------------------ routing units


def test_models_view_and_per_group_limits(zoo):
    """models() is the per-family replica-group view: replica counts,
    liveness, the capability flags from /healthz, and each group's own
    admission limit."""
    router = ServingRouter([_gpt(zoo), _gpt(zoo), _ernie(zoo), _vit(zoo)])
    groups = router.models()
    assert sorted(groups) == ["ernie", "gpt", "vit"]
    assert groups["gpt"]["replicas"] == [0, 1] and groups["gpt"]["live"] == 2
    assert groups["ernie"]["replicas"] == [2]
    for fam, info in groups.items():
        assert info["capabilities"]["family"] == fam
        assert isinstance(info["limit"], int) and info["limit"] > 1
    assert groups["gpt"]["capabilities"]["has_kv_cache"] is True
    assert groups["vit"]["capabilities"]["emits"] == "floats"
    assert groups["ernie"]["capabilities"]["has_kv_cache"] is False
    # per-group limits differ: an image is far bigger than a text cap
    assert groups["vit"]["limit"] == 8 * 8 * 3 + 1
    assert groups["gpt"]["limit"] <= 64


def test_unknown_model_is_a_clean_submit_reject(zoo):
    """An unserved family never becomes a queued request — submit-time
    ValueError naming what IS served."""
    router = ServingRouter([_gpt(zoo), _vit(zoo)])
    with pytest.raises(ValueError, match="bert"):
        router.submit(GPT_PROMPTS[0], max_length=4, model="bert")
    with pytest.raises(ValueError, match="not servable by any"):
        # fits the vit group's limit but names gpt: per-GROUP bound
        router.submit(np.ones(100, np.int32), max_length=4, model="gpt")
    assert router.drain() == {}


def test_dispatch_never_crosses_families(zoo):
    """Mixed three-family traffic through one router: every request
    lands on its own family's replica (asserted on every prompt each
    engine saw) and every family's results match a lone engine."""
    ref_gpt_eng = _gpt(zoo)
    rids = [ref_gpt_eng.submit(p, max_length=8) for p in GPT_PROMPTS]
    ref_res = ref_gpt_eng.drain()
    ref_gpt = [np.asarray(ref_res[r].tokens) for r in rids]

    ref_vit_eng = _vit(zoo)
    vr = ref_vit_eng.submit(encode_floats(_image()))
    ref_bits = np.asarray(ref_vit_eng.drain()[vr].tokens)

    ref_ernie_eng = _ernie(zoo)
    blank = np.asarray([5, 3, 9, 11], np.int32)  # mask id 3 at pos 1
    er = ref_ernie_eng.submit(blank)
    ref_blank = np.asarray(ref_ernie_eng.drain()[er].tokens)

    engines = [_gpt(zoo), _ernie(zoo), _vit(zoo)]
    seen = {i: [] for i in range(3)}
    for i, eng in enumerate(engines):
        orig = eng.submit

        def tap(prompt, _orig=orig, _i=i, **kw):
            seen[_i].append(int(np.asarray(prompt).size))
            return _orig(prompt, **kw)

        eng.submit = tap
    router = ServingRouter(engines)
    # default model = replica 0's family (gpt): no model kwarg needed
    g0 = router.submit(GPT_PROMPTS[0], max_length=8)
    g1 = router.submit(GPT_PROMPTS[1], max_length=8, model="gpt")
    e0 = router.submit(blank, model="ernie")
    v0 = router.submit(encode_floats(_image()), model="vit")
    res = router.drain()
    assert len(res) == 4
    assert np.array_equal(res[g0].tokens, ref_gpt[0])
    assert np.array_equal(res[g1].tokens, ref_gpt[1])
    assert np.array_equal(res[e0].tokens, ref_blank)
    assert res[e0].finish_reason == "complete"
    assert np.array_equal(res[v0].tokens, ref_bits)
    assert decode_floats(res[v0].tokens).size == 32
    # the dispatch log: gpt saw only text sizes, ernie only the blank,
    # vit only image-sized wire payloads
    assert seen[0] and all(n < 16 for n in seen[0])
    assert seen[1] == [blank.size]
    assert seen[2] == [8 * 8 * 3]


def test_failover_stays_inside_the_model_group(zoo):
    """A GPT replica killed mid-stream on a 2-GPT + 1-vit fleet:
    migration lands on the SURVIVING GPT replica (byte parity proves
    it — the vit replica cannot decode text), vit traffic unaffected."""
    faults.configure(replica_kill="0:3")
    ref_eng = _gpt(zoo)
    rids = [ref_eng.submit(p, max_length=8) for p in GPT_PROMPTS]
    ref_res = ref_eng.drain()
    ref = [np.asarray(ref_res[r].tokens) for r in rids]
    try:
        router = ServingRouter([_gpt(zoo), _gpt(zoo), _vit(zoo)],
                               probe_every=1)
        g = [router.submit(p, max_length=8, model="gpt")
             for p in GPT_PROMPTS]
        v = router.submit(encode_floats(_image()), model="vit")
        res = router.drain(max_ticks=400)
    finally:
        faults.reset()
    assert len(res) == 3
    for rid, want in zip(g, ref):
        assert np.array_equal(np.asarray(res[rid].tokens), want)
    assert res[v].finish_reason == "complete"
    assert get_event_log().find("replica_dead", replica=0)
    assert router.metrics.snapshot()["replica_deaths"] == 1
    groups = router.models()
    assert groups["gpt"]["live"] == 1 and groups["vit"]["live"] == 1


def test_group_stranding_is_per_model(zoo):
    """The whole GPT group dead strands ONLY gpt requests ("error" +
    router_stranded naming the family); the embedding group finishes
    its work untouched."""
    gpt_eng = _gpt(zoo)
    router = ServingRouter([gpt_eng, _vit(zoo)], probe_every=1)
    g = router.submit(GPT_PROMPTS[0], max_length=8, model="gpt")
    v = router.submit(encode_floats(_image()), model="vit")
    gpt_eng.declare_dead()
    res = router.drain(max_ticks=400)
    assert res[g].finish_reason == "error"
    assert res[v].finish_reason == "complete"
    ev = get_event_log().find("router_stranded")
    assert ev and "gpt" in ev[-1].attrs["models"]
    assert "vit" not in ev[-1].attrs["models"]


def test_probe_refreshes_capability_advertisement(zoo):
    """The health probe carries model + capabilities; the router's
    group view survives probing a live fleet (the scrape IS the
    advertisement channel)."""
    router = ServingRouter([_gpt(zoo), _vit(zoo)], probe_every=1)
    for _ in range(3):
        router.step()
    groups = router.models()
    assert groups["gpt"]["capabilities"]["family"] == "gpt"
    assert groups["vit"]["capabilities"]["emits"] == "floats"
    states = list(router.replica_states)
    assert states == ["ok", "ok"]


# ------------------------------------------------------------ the API


def _post(url, body):
    req = urllib.request.Request(url, json.dumps(body).encode(),
                                 {"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=60)


def test_api_models_and_embeddings_over_hetero_fleet(zoo):
    """/v1/models derives from the replica groups and /v1/embeddings
    fronts the embedding family: float vectors out, bit-identical to
    the engine wire, defaulting to the only float-out family."""
    emb_ref = _vit(zoo)
    img = _image()
    rr = emb_ref.submit(encode_floats(img))
    want = decode_floats(emb_ref.drain()[rr].tokens)

    router = ServingRouter([_gpt(zoo), _ernie(zoo), _vit(zoo)])
    api = ApiServer(router, model_id="fleet-hetero").start()
    try:
        with urllib.request.urlopen(api.url + "/v1/models",
                                    timeout=30) as r:
            listing = json.loads(r.read())
        ids = [m["id"] for m in listing["data"]]
        assert ids[0] == "fleet-hetero"
        assert listing["data"][0]["group"] == "gpt"
        assert sorted(ids[1:]) == ["ernie", "gpt", "vit"]
        by_id = {m["id"]: m for m in listing["data"][1:]}
        assert by_id["vit"]["capabilities"]["emits"] == "floats"
        assert by_id["gpt"]["replicas"] == [0] and by_id["gpt"]["live"] == 1

        # single vector, model defaulted (vit is the only float-out)
        with _post(api.url + "/v1/embeddings",
                   {"input": [float(v) for v in img.reshape(-1)]}) as r:
            out = json.loads(r.read())
        assert out["model"] == "vit" and len(out["data"]) == 1
        got = np.asarray(out["data"][0]["embedding"], np.float32)
        assert np.array_equal(got, want), "API vector != engine bits"

        # batch form keeps per-row order
        with _post(api.url + "/v1/embeddings",
                   {"model": "vit",
                    "input": [[float(v) for v in img.reshape(-1)],
                              [float(v) for v in _image(1).reshape(-1)]]}
                   ) as r:
            out = json.loads(r.read())
        assert [d["index"] for d in out["data"]] == [0, 1]
        assert np.array_equal(
            np.asarray(out["data"][0]["embedding"], np.float32), want)

        # family-addressed completion through the same front door
        with _post(api.url + "/v1/completions",
                   {"model": "gpt", "prompt": [1, 2, 3],
                    "max_tokens": 4}) as r:
            comp = json.loads(r.read())
        assert comp["choices"][0]["finish_reason"] == "length"

        # unknown embedding family → structured 404, not an exception
        try:
            _post(api.url + "/v1/embeddings",
                  {"model": "resnet", "input": [1.0, 2.0]})
            raise AssertionError("unknown family did not 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
            assert json.loads(e.read())["error"]["type"] == "model_not_found"

        # a text family is not an embedding endpoint
        try:
            _post(api.url + "/v1/embeddings",
                  {"model": "gpt", "input": [1.0, 2.0]})
            raise AssertionError("token-out family did not 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        api.stop()
