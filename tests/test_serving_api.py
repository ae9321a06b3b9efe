"""Deployable front door suite: OpenAI-compatible API, replica RPC,
fleet launcher (docs/SERVING.md "Deployment").

The load-bearing assertions mirror the router chaos suite's, one
process boundary further out: BYTE IDENTITY between what the HTTP/SSE
surface streams and what the in-process engine decodes (greedy AND
seeded sampling — the RNG-key wire codec is exact), the structured 4xx
table (a bad request is a JSON error, never an engine exception), and
the network-failure mapping that lets ``ServingRouter`` treat an
unreachable replica process exactly like a killed in-process replica
(zero-token-loss migration over RPC, crc32-checked KV handoff over
RPC, exactly-one-result conservation).

Everything except the subprocess fleet e2e (slow-marked; tier-1 covers
the same router/API/RPC contracts in-process below) runs on CPU in
seconds and carries the ``chaos`` marker like the router suite."""

import gc
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from serving_parity import sharing_programs

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.obs import get_event_log
from fleetx_tpu.resilience.faults import RPCFault, FaultPlan, faults
from fleetx_tpu.serving import QueueFull, ServingEngine, ServingRouter
from fleetx_tpu.serving.api import wire
from fleetx_tpu.serving.api.replica_client import ReplicaClient
from fleetx_tpu.serving.api.replica_server import ReplicaServer
from fleetx_tpu.serving.api.server import ApiServer

pytestmark = pytest.mark.chaos

PROMPTS = [np.asarray([1, 2, 3], np.int32),
           np.asarray([4, 5, 6, 7, 8], np.int32),
           np.asarray([9, 10], np.int32)]

GEN = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                       pad_token_id=60, max_length=8)


@pytest.fixture(scope="module")
def tiny():
    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return model, params


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    get_event_log().clear()
    yield
    faults.reset()
    # engines this module parked in "draining" unregister their global
    # health probes only when collected (weakref.finalize); the HTTP
    # server machinery leaves reference cycles, so collect NOW — a
    # stale draining probe must not leak into a later module's
    # aggregate healthz_payload() assertions
    gc.collect()


@sharing_programs
def _engine(tiny, **kw):
    model, params = tiny
    return ServingEngine(model, params, slots=kw.pop("slots", 3),
                         cache_len=kw.pop("cache_len", 32),
                         gen_cfg=kw.pop("gen_cfg", GEN), prefill_bucket=4,
                         page_size=kw.pop("page_size", 8), **kw)


@pytest.fixture(scope="module")
def ref_tokens(tiny):
    """Reference tokens from ONE plain in-process engine: greedy for
    each of PROMPTS plus the seeded-sampling stream for PROMPTS[1].
    Batch composition never changes greedy tokens, and an explicit
    ``seed=`` pins the sampling RNG independent of request id — so one
    engine build serves every parity test in the module."""
    eng = _engine(tiny)
    rids = [eng.submit(p) for p in PROMPTS]
    srid = eng.submit(PROMPTS[1], decode_strategy="sampling",
                      temperature=0.7, top_p=0.9, seed=123)
    res = eng.drain()
    greedy = [[int(t) for t in res[r].tokens] for r in rids]
    return greedy, [int(t) for t in res[srid].tokens]


def _post(url, body):
    req = urllib.request.Request(url, json.dumps(body).encode(),
                                 {"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=60)


def _read_sse(resp):
    """(token ids, finish_reason, concatenated text) off one SSE body."""
    toks, finish, text = [], None, []
    for line in resp:
        line = line.decode().strip()
        if not line.startswith("data: ") or line[6:] == "[DONE]":
            continue
        chunk = json.loads(line[6:])
        if "token" in chunk:
            toks.append(chunk["token"])
        choice = chunk["choices"][0]
        text.append(choice.get("delta", {}).get("content",
                                                choice.get("text", "")) or "")
        if choice["finish_reason"]:
            finish = choice["finish_reason"]
    return toks, finish, "".join(text)


# ---------------------------------------------------------------- wire


def test_wire_codecs_roundtrip_exact():
    """RNG keys (raw and typed), KV blobs, and results survive the JSON
    wire byte-exactly — the substance behind cross-process RNG-exact
    sampling and crc32-intact KV handoff."""
    raw = jax.random.PRNGKey(42)
    words = wire.rng_key_to_wire(raw)
    assert json.loads(json.dumps(words)) == words  # JSON-exact ints
    back = wire.rng_key_from_wire(words)
    assert np.array_equal(np.asarray(raw), np.asarray(back))

    typed = jax.random.key(7)  # new-style opaque-dtype key
    back2 = wire.rng_key_from_wire(wire.rng_key_to_wire(typed))
    assert np.array_equal(np.asarray(jax.random.key_data(typed)),
                          np.asarray(back2))
    assert wire.rng_key_to_wire(None) is None

    blobs = [b"\x00\xffpage0", b"page1\x01"]
    assert wire.b64_blobs_decode(wire.b64_blobs_encode(blobs)) == blobs

    from fleetx_tpu.serving.engine import ServingResult

    res = ServingResult(id=3, prompt=np.asarray([1, 2], np.int32),
                        tokens=np.asarray([4, 5, 6], np.int32),
                        finish_reason="eos", ttft_s=0.5, latency_s=1.5)
    back3 = wire.result_from_wire(
        json.loads(json.dumps(wire.result_to_wire(res))))
    assert back3.id == 3 and back3.finish_reason == "eos"
    assert np.array_equal(back3.tokens, res.tokens)
    assert np.array_equal(back3.prompt, res.prompt)


def test_wire_error_kinds_roundtrip():
    """Typed engine refusals cross the wire as themselves."""
    from fleetx_tpu.serving.engine import QueueFull as QF
    from fleetx_tpu.serving.engine import ShuttingDown

    assert wire.kind_for_exception(QF("x")) == "queue_full"
    assert wire.kind_for_exception(ValueError("x")) == "value_error"
    assert wire.kind_for_exception(RuntimeError("x")) == "internal"
    with pytest.raises(QF):
        wire.raise_for_kind("queue_full", "full")
    with pytest.raises(ShuttingDown):
        wire.raise_for_kind("shutting_down", "bye")
    with pytest.raises(RuntimeError):
        wire.raise_for_kind("no_such_kind", "?")


# ------------------------------------------------------------ API layer


def test_sse_stream_byte_identical_greedy_and_sampled(tiny, ref_tokens):
    """The acceptance bar: tokens streamed over SSE — greedy AND seeded
    sampling — are byte-identical to the in-process engine's, and the
    aggregate (non-stream) response carries the same tokens."""
    direct_greedy, direct_sampled = ref_tokens[0][0], ref_tokens[1]
    api = ApiServer(_engine(tiny), model_id="m").start()
    try:
        with _post(api.url + "/v1/chat/completions",
                   {"messages": [{"role": "user", "content": "1 2 3"}],
                    "stream": True}) as r:
            assert r.headers["Content-Type"].startswith("text/event-stream")
            toks, finish, text = _read_sse(r)
        assert toks == direct_greedy
        assert finish == "length"
        assert text.split() == [str(t) for t in direct_greedy]

        with _post(api.url + "/v1/completions",
                   {"prompt": "4 5 6 7 8", "stream": True,
                    "temperature": 0.7, "top_p": 0.9, "seed": 123}) as r:
            toks, finish, _ = _read_sse(r)
        assert toks == direct_sampled

        with _post(api.url + "/v1/chat/completions",
                   {"messages": [{"role": "user", "content": "1 2 3"}]}) as r:
            body = json.loads(r.read())
        assert body["tokens"] == direct_greedy
        assert body["object"] == "chat.completion"
        assert body["choices"][0]["finish_reason"] == "length"
        assert (body["choices"][0]["message"]["content"].split()
                == [str(t) for t in direct_greedy])
        assert body["usage"]["completion_tokens"] == len(direct_greedy)
    finally:
        api.stop()


def test_api_4xx_table_and_models_contract(tiny):
    """Every malformed request maps to a structured 4xx JSON error —
    the engine never sees it (or refuses it safely) — and /v1/models
    serves the OpenAI listing shape."""
    api = ApiServer(_engine(tiny), model_id="fleetx-test").start()
    try:
        cases = [
            (400, "/v1/chat/completions", {}),
            (400, "/v1/chat/completions", {"messages": []}),
            (400, "/v1/chat/completions", {"messages": ["hi"]}),
            (400, "/v1/chat/completions",
             {"messages": [{"role": "user", "content": "not ids"}]}),
            (400, "/v1/completions", {}),
            (400, "/v1/completions", {"prompt": ""}),
            (400, "/v1/completions", {"prompt": "1 2", "temperature": -1}),
            (400, "/v1/completions", {"prompt": "1 2", "top_p": 0}),
            (400, "/v1/completions", {"prompt": "1 2", "top_p": 1.5}),
            (400, "/v1/completions", {"prompt": "1 2", "top_k": 0}),
            (400, "/v1/completions", {"prompt": "1 2", "max_tokens": 0}),
            (400, "/v1/completions", {"prompt": "1 2", "max_tokens": "8"}),
            (400, "/v1/completions", {"prompt": "1 2", "n": 2}),
            (400, "/v1/completions", {"prompt": "1 2", "seed": "abc"}),
            (400, "/v1/completions", {"prompt": "1 2", "stream": "yes"}),
            # engine-level refusal surfaced as 400, not a 500
            (400, "/v1/completions", {"prompt": " ".join(["1"] * 99)}),
            (404, "/v1/chat/completions",
             {"model": "gpt-4",
              "messages": [{"role": "user", "content": "1"}]}),
            (404, "/v1/embeddings", {"input": "1"}),
        ]
        for code, path, body in cases:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(api.url + path, body)
            assert ei.value.code == code, (path, body)
            err = json.loads(ei.value.read())
            assert err["error"]["message"], (path, body)

        # malformed JSON body → 400, never a handler crash
        req = urllib.request.Request(
            api.url + "/v1/completions", b"{not json",
            {"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400

        with urllib.request.urlopen(api.url + "/v1/models",
                                    timeout=30) as r:
            models = json.loads(r.read())
        assert models["object"] == "list"
        assert [m["id"] for m in models["data"]] == ["fleetx-test"]
        assert models["data"][0]["object"] == "model"
    finally:
        api.stop()


# ------------------------------------------------------- replica RPC


def test_rpc_router_byte_parity_and_migration(tiny, ref_tokens):
    """A router over cross-process-shaped RPC replicas decodes byte-
    identically to a plain engine; stopping a replica server mid-burst
    migrates its requests with zero token loss (exactly-one-result)."""
    direct = ref_tokens[0]

    servers = [ReplicaServer(_engine(tiny)).start() for _ in range(2)]
    try:
        clients = [ReplicaClient(s.url, connect_wait_s=5) for s in servers]
        assert clients[0].paged and clients[0].page_size == 8
        assert clients[0].cache_len == 32
        assert clients[0].model.cfg.max_position_embeddings == 64

        router = ServingRouter(clients, probe_every=1)
        streams = {}
        rids = [router.submit(p, max_length=8,
                              on_token=lambda rid, t, f, i=i:
                              streams.setdefault(i, []).append(int(t)))
                for i, p in enumerate(PROMPTS)]
        # run a few ticks, then hard-stop one replica server (the HTTP
        # equivalent of a process dying under the router)
        for _ in range(3):
            router.step()
        servers[0].stop()
        res = router.drain(max_ticks=500)
        assert len(res) == len(PROMPTS), "lost or duplicated a request"
        for i, rid in enumerate(rids):
            assert [int(t) for t in res[rid].tokens] == direct[i], (
                f"request {i} diverged after replica-server stop")
            assert streams[i] == direct[i], (
                f"request {i} stream lost/duplicated tokens")
        ev = get_event_log()
        assert ev.find("request_migrated"), "no migration event banked"
        # the hedged migration already saved the requests; keep ticking
        # so the probe ladder finishes escalating the unreachable
        # replica to DEAD (backoffed re-probes need a few idle ticks)
        for _ in range(64):
            if ev.find("replica_dead"):
                break
            router.step()
        assert ev.find("replica_dead"), "router never marked the dead RPC replica"
    finally:
        for s in servers:
            s.stop()


def test_rpc_disagg_kv_handoff(tiny, ref_tokens):
    """Prefill→decode KV handoff works over the RPC boundary: the
    crc32-trailed v2 wire blobs ship base64 through HTTP and admit
    byte-identically on the decode replica."""
    direct = ref_tokens[0]
    servers = [ReplicaServer(_engine(tiny, role="prefill")).start(),
               ReplicaServer(_engine(tiny, role="decode")).start()]
    try:
        clients = [ReplicaClient(s.url, connect_wait_s=5) for s in servers]
        assert [c.role for c in clients] == ["prefill", "decode"]
        router = ServingRouter(clients, probe_every=1)
        rids = [router.submit(p, max_length=8) for p in PROMPTS]
        res = router.drain(max_ticks=500)
        assert len(res) == len(PROMPTS)
        for i, rid in enumerate(rids):
            assert [int(t) for t in res[rid].tokens] == direct[i]
        ev = get_event_log()
        assert ev.find("kv_shipped"), "no kv_shipped event over RPC"
    finally:
        for s in servers:
            s.stop()


def test_rpc_failure_mapping_unreachable(tiny):
    """The decided network-failure table: each client method maps an
    unreachable replica onto the router's existing fallback types."""
    server = ReplicaServer(_engine(tiny)).start()
    client = ReplicaClient(server.url, connect_wait_s=5)
    server.stop()  # replica process "dies"

    from fleetx_tpu.resilience.faults import ReplicaKilled

    with pytest.raises(QueueFull):
        client.submit([1, 2])
    with pytest.raises(ReplicaKilled):
        client.step()
    with pytest.raises(ConnectionError):
        client.health()
    with pytest.raises(ConnectionError):
        client.export_kv(0)
    assert client.take_result(0) is None
    assert client.emitted_tokens(0) is None
    assert client.prefilled_ready() == []
    assert client.cancel(0) is False
    client.request_shutdown()  # swallowed: already down
    client.declare_dead()


def test_rpc_typed_errors_cross_the_wire(tiny):
    """Replica-side refusals arrive as the same exception types the
    in-process router catches (ValueError table included)."""
    server = ReplicaServer(_engine(tiny)).start()
    try:
        client = ReplicaClient(server.url, connect_wait_s=5)
        with pytest.raises(ValueError, match="empty"):
            client.submit([])
        with pytest.raises(ValueError):
            client.submit(list(range(40)))  # >= cache_len budget
        with pytest.raises(KeyError):
            client.export_kv(12345)  # not a parked prefill
        # shutdown flips subsequent submits to ShuttingDown over HTTP
        from fleetx_tpu.serving.engine import ShuttingDown

        client.request_shutdown(0.0)
        with pytest.raises(ShuttingDown):
            client.submit([1, 2, 3])
    finally:
        server.stop()


def test_rpc_fault_injectors(tiny, monkeypatch):
    """FLEETX_FAULT_RPC_DROP/_DELAY: the on_rpc seam drops (typed
    ConnectionError) or delays by selector, counts injections, and
    parses from the environment with the house selector grammar."""
    server = ReplicaServer(_engine(tiny)).start()
    try:
        client = ReplicaClient(server.url, connect_wait_s=5)

        faults.configure(rpc_drop="0")
        with pytest.raises(RPCFault):
            client.health()
        # RPCFault IS a ConnectionError → the sentinel mapping applies
        faults.configure(rpc_drop="0")
        assert client.take_result(0) is None
        assert faults.injected["rpc_drop"] == 1  # configure() resets
        faults.reset()

        faults.configure(rpc_delay="0", rpc_delay_s=0.2)
        t0 = time.monotonic()
        client.health()
        assert time.monotonic() - t0 >= 0.2
        client.health()  # selector exhausted: no second delay
        assert faults.injected["rpc_delay"] == 1
        faults.reset()

        monkeypatch.setenv("FLEETX_FAULT_RPC_DROP", "2+")
        monkeypatch.setenv("FLEETX_FAULT_RPC_DELAY", "0")
        monkeypatch.setenv("FLEETX_FAULT_RPC_DELAY_S", "0.01")
        plan = FaultPlan.from_env()
        assert plan is not None
        assert plan.rpc_drop == "2+" and plan.rpc_delay == "0"
        assert plan.rpc_delay_s == 0.01
    finally:
        faults.reset()
        server.stop()


def test_api_healthz_tracks_router_and_engine(tiny):
    """/healthz on the front door: engine target serves its drain-aware
    health dict; router target aggregates replica states."""
    eng = _engine(tiny)
    api = ApiServer(eng).start()
    try:
        with urllib.request.urlopen(api.url + "/healthz", timeout=30) as r:
            assert json.loads(r.read())["state"] == "ok"
        eng.request_shutdown(0.0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(api.url + "/healthz", timeout=30)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["state"] == "draining"
    finally:
        api.stop()


# ------------------------------------------------- fleet launcher e2e


@pytest.mark.slow  # ~60s: spawns real replica subprocesses; tier-1 covers
# the same router/RPC/API contracts in-process via the tests above, and
# tools/chaos_check.py serving_http kills a real process mid-stream
def test_serve_fleet_e2e_smoke(tmp_path):
    """tools/serve.py end to end: spawn a 2-replica fleet, stream a
    chat completion byte-identically, SIGTERM drains to exit 0."""
    import os
    import signal
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    pf = str(tmp_path / "api.port")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "tools/serve.py", "--demo", "--replicas", "2",
         "--port", "0", "--api-port-file", pf, "--grace-s", "10"],
        cwd=repo, env=env)
    try:
        deadline = time.monotonic() + 180
        while not (tmp_path / "api.port").exists():
            assert proc.poll() is None, "launcher died during startup"
            assert time.monotonic() < deadline, "API port never published"
            time.sleep(0.1)
        base = f"http://127.0.0.1:{int((tmp_path / 'api.port').read_text())}"
        with _post(base + "/v1/chat/completions",
                   {"model": "fleetx-demo", "stream": True,
                    "messages": [{"role": "user", "content": "1 2 3"}]}) as r:
            toks, finish, _ = _read_sse(r)
        assert len(toks) == 8 and finish == "length"
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            h = json.loads(r.read())
        assert h["state"] == "ok" and len(h["replicas"]) == 2
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
