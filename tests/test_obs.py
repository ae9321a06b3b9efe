"""Unified observability layer (fleetx_tpu/obs/, docs/OBSERVABILITY.md):
registry semantics, bounded reservoirs, span tracing + profiler bridge,
structured events, HTTP exposition incl. the drain-aware /healthz, and
the Trainer's MFU-bearing TRAIN log line."""

import json
import logging
import urllib.error
import urllib.request

import numpy as np
import pytest

from fleetx_tpu.obs import (
    EventLog,
    MetricsRegistry,
    ObsServer,
    SpanRecorder,
    register_health,
    span,
    unregister_health,
)


@pytest.fixture(autouse=True)
def _flush_stale_health_probes():
    """Engines unregister their global /healthz probe via weakref.finalize,
    i.e. only once gc actually collects them — a draining/dead engine from
    an earlier test module can linger until then and flip this module's
    healthz assertions to 503 (same flake class test_serving_api.py guards
    against). Collect up front so only probes registered by THIS test are
    live."""
    import gc

    gc.collect()
    yield


# ------------------------------------------------------------- registry


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("fleetx_t_total", "help", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    assert c.labels(kind="a").value == 3
    assert c.labels(kind="b").value == 1
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)  # counters are monotonic
    g = reg.gauge("fleetx_t_depth")
    g.set(5)
    g.inc(-2)
    assert g.value == 3
    h = reg.histogram("fleetx_t_seconds", reservoir_cap=100)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    solo = h.labels()
    assert solo.count == 4 and solo.sum == 10.0
    assert solo.mean == 2.5 and solo.min == 1.0 and solo.max == 4.0
    assert solo.percentile(50) == pytest.approx(2.5)


def test_registry_rejects_bad_names_and_kind_conflicts():
    reg = MetricsRegistry()
    for bad in ("CamelCase", "has-dash", "1leading", ""):
        with pytest.raises(ValueError):
            reg.counter(bad)
    reg.counter("fleetx_t_total")
    # same name + same shape = same family (idempotent registration)
    assert reg.counter("fleetx_t_total") is reg.counter("fleetx_t_total")
    with pytest.raises(ValueError):
        reg.gauge("fleetx_t_total")  # kind conflict
    with pytest.raises(ValueError):
        reg.counter("fleetx_t_total", labelnames=("x",))  # label conflict
    with pytest.raises(ValueError):
        reg.counter("fleetx_t_x", labelnames=("Bad",))


def test_histogram_reservoir_is_bounded_but_sum_exact():
    reg = MetricsRegistry()
    h = reg.histogram("fleetx_t_seconds", reservoir_cap=64).labels()
    for i in range(10_000):
        h.observe(float(i))
    assert len(h.reservoir) == 64          # bounded forever
    assert h.count == 10_000               # exact accounting survives
    assert h.sum == sum(range(10_000))
    assert h.max == 9999.0 and h.min == 0.0
    # percentiles describe the newest window, not ancient history
    assert h.percentile(50) > 9000


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("fleetx_t_total", "a counter", ("engine",)).labels(
        engine="0").inc(7)
    reg.gauge("fleetx_t_depth", "a gauge").set(3)
    h = reg.histogram("fleetx_t_seconds", "a dist")
    h.observe(0.25)
    text = reg.prometheus_text()
    assert '# TYPE fleetx_t_total counter' in text
    assert 'fleetx_t_total{engine="0"} 7' in text
    assert 'fleetx_t_depth 3' in text
    # histograms expose as summaries: quantiles + exact sum/count
    assert '# TYPE fleetx_t_seconds summary' in text
    assert 'fleetx_t_seconds{quantile="0.5"} 0.25' in text
    assert 'fleetx_t_seconds_count 1' in text
    snap = reg.snapshot()
    json.dumps(snap)  # JSON-safe by contract
    assert snap["fleetx_t_seconds"]["series"][0]["count"] == 1


# -------------------------------------------------------------- tracing


def test_spans_nest_and_export_chrome_trace():
    rec = SpanRecorder(capacity=16)
    with span("train.step", recorder=rec, step=3):
        with span("train.data", recorder=rec):
            pass
    spans = rec.spans()
    # inner closes first; depth reflects nesting at close time
    assert [(s.name, s.depth) for s in spans] == [
        ("train.data", 1), ("train.step", 0)]
    trace = rec.chrome_trace()
    evs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in evs} == {"train.step", "train.data"}
    step = next(e for e in evs if e["name"] == "train.step")
    data = next(e for e in evs if e["name"] == "train.data")
    assert step["args"]["step"] == 3
    # the child's interval sits inside the parent's
    assert step["ts"] <= data["ts"]
    assert data["ts"] + data["dur"] <= step["ts"] + step["dur"] + 1e-3
    json.dumps(trace)


def test_span_ring_is_bounded_and_survives_exceptions():
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        try:
            with span("serving.tick", recorder=rec, i=i):
                if i % 2:
                    raise RuntimeError("tick fault")
        except RuntimeError:
            pass
    assert len(rec.spans()) == 8        # ring bounded
    assert rec.dropped == 12
    # the raising spans still recorded (rollback paths stay observable)
    assert [s.attrs["i"] for s in rec.spans()] == list(range(12, 20))


@pytest.mark.slow  # 14.8s (PR 18 tier-1 budget audit): spins up the
# real jax profiler just to see the bridge's annotation land in a
# Chrome trace. The span contract itself (nesting, export, bounded
# ring, exception safety) stays tier-1 via
# test_spans_nest_and_export_chrome_trace and
# test_span_ring_is_bounded_and_survives_exceptions; only the
# profiler-integration acceptance rides the slow tier.
def test_trace_annotation_bridge_reaches_profiler_trace(tmp_path):
    """Acceptance: host-side spans appear in a jax profiler Chrome trace
    via the TraceAnnotation bridge (so serving/train phases line up with
    XLA kernels in the same timeline)."""
    import glob
    import gzip

    import jax

    jax.profiler.start_trace(str(tmp_path))
    with span("obs.bridge.probe"):
        float(jax.numpy.ones(8).sum())  # some device work inside the span
    jax.profiler.stop_trace()
    traces = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.trace.json.gz"))
    assert traces, "profiler wrote no trace"
    blob = b"".join(gzip.open(t, "rb").read() for t in traces)
    assert b"obs.bridge.probe" in blob


# --------------------------------------------------------------- events


def test_event_log_bounded_query_and_counter():
    reg = MetricsRegistry()
    log = EventLog(capacity=4, registry=reg)
    for i in range(6):
        log.emit("sentry_skip", step=i)
    log.emit("poison_retired", request=7)
    assert len(log) == 4  # bounded window
    assert [e.attrs["step"] for e in log.find("sentry_skip")] == [3, 4, 5]
    assert log.last("poison_retired").attrs["request"] == 7
    assert log.find("poison_retired", request=8) == []
    assert log.counts() == {"sentry_skip": 3, "poison_retired": 1}
    # lifetime counts survive window eviction via the registry counter
    fam = reg.counter("fleetx_events_total", labelnames=("kind",))
    assert fam.labels(kind="sentry_skip").value == 6
    with pytest.raises(ValueError):
        log.emit("Not Snake")
    json.dumps(log.snapshot())


# ----------------------------------------------------------------- http


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read()


def test_http_endpoints_and_drain_aware_healthz():
    from fleetx_tpu.obs import emit

    emit("obs_http_probe")  # guarantee the global registry has a series
    srv = ObsServer(port=0).start()
    try:
        status, body = _get(srv.url + "/metrics")
        assert status == 200
        assert b"fleetx_events_total" in body  # global registry serves
        status, body = _get(srv.url + "/snapshot")
        snap = json.loads(body)
        assert {"metrics", "events", "health", "spans"} <= set(snap)
        status, body = _get(srv.url + "/trace")
        assert "traceEvents" in json.loads(body)
        status, _ = _get(srv.url + "/healthz")
        assert status == 200
        register_health("test_probe", lambda: False)
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                _get(srv.url + "/healthz")
            assert exc.value.code == 503
            payload = json.loads(exc.value.read())
            assert "test_probe" in payload["failing"]
        finally:
            unregister_health("test_probe")
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.url + "/nope")
        assert exc.value.code == 404
    finally:
        srv.stop()


# ----------------------------------------- serving metrics on the registry


def test_serving_metrics_reservoirs_capped_after_10k_retires():
    """Regression (ISSUE 9 satellite): the old ServingMetrics kept
    ttft_s/queue_wait_s/latency_s/pages_per_request as grow-forever
    lists; on the registry every distribution is a bounded reservoir, so
    a 10k-retire loop must hold them at the cap while counters and
    snapshot aggregates stay exact."""
    from fleetx_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics(slots=2)
    for i in range(10_000):
        m.record_submit()
        m.record_admit(0.001)
        m.record_first_token(0.002)
        m.record_tokens(3)
        m.record_prefix(4, 8, 1)
        m.observe_tick(1, 2, tick_s=0.0005)
        m.observe_pages(5, 10)
        m.record_retire(0.01, "eos")
    cap = 4096  # FLEETX_OBS_RESERVOIR default
    for res in (m.ttft_s, m.queue_wait_s, m.latency_s, m.tick_s,
                m.pages_per_request):
        assert len(res) <= cap, len(res)
    s = m.snapshot()
    assert s["submitted"] == s["admitted"] == s["retired"] == 10_000
    assert s["tokens_generated"] == 30_000
    assert s["ticks"] == 10_000
    assert s["finish_reasons"] == {"eos": 10_000}
    assert s["prefill_tokens_saved"] == 40_000  # exact despite the cap
    assert s["pages_per_request_mean"] == pytest.approx(1.0)
    assert s["slot_occupancy_mean"] == pytest.approx(1.0)
    assert s["page_occupancy_peak"] == pytest.approx(0.5)
    json.dumps(s)


def test_live_engine_exposes_prometheus_and_flips_healthz():
    """Acceptance: GET /metrics on a live ServingEngine returns
    Prometheus text with queue depth, occupancy, TTFT/tick histograms
    and recovery/poison counters; /healthz flips to 503 after
    request_shutdown()."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
    from fleetx_tpu.serving import ServingEngine

    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    eng = ServingEngine(
        model, params, slots=2, cache_len=16, prefill_bucket=4,
        gen_cfg=GenerationConfig(decode_strategy="greedy",
                                 eos_token_id=10**6, pad_token_id=60,
                                 max_length=4))
    eng.submit(np.asarray([1, 2, 3], np.int32), max_length=4)
    eng.drain()
    srv = ObsServer(port=0).start()
    try:
        _, body = _get(srv.url + "/metrics")
        text = body.decode()
        lab = f'engine="{eng.metrics.engine_label}"'
        for name in ("fleetx_serving_queue_depth",
                     "fleetx_serving_active_slots_per_tick",
                     "fleetx_serving_ttft_seconds",
                     "fleetx_serving_tick_seconds",
                     "fleetx_serving_engine_recoveries_total",
                     "fleetx_serving_poison_retired_total",
                     "fleetx_serving_retired_total"):
            assert f"{name}" in text, f"{name} missing from /metrics"
        assert f'fleetx_serving_ttft_seconds_count{{{lab}}} 1' in text
        assert f'fleetx_serving_retired_total{{{lab},reason="max_length"}}' \
            in text
        status, _ = _get(srv.url + "/healthz")
        assert status == 200
        eng.request_shutdown(grace_s=0.0)
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.url + "/healthz")
        assert exc.value.code == 503  # the router's rotate-me-out signal
        eng.shutdown(grace_s=0.0)
    finally:
        srv.stop()


def test_serving_metrics_series_removed_on_gc():
    """Per-engine labeled series are dropped from the registry when the
    ServingMetrics instance dies — a process cycling engines must not
    accumulate dead-engine series in /metrics forever."""
    import gc

    from fleetx_tpu.serving.metrics import ServingMetrics

    reg = MetricsRegistry()
    m = ServingMetrics(slots=2, registry=reg)
    m.record_submit()
    m.record_retire(0.01, "eos")
    m.observe_tick(1, 1, 0.001)
    assert any(fam.series() for fam in reg.families())
    del m
    gc.collect()
    leftover = [(fam.name, labels) for fam in reg.families()
                for labels, _ in fam.series()]
    assert not leftover, leftover


def test_healthz_json_body_carries_rotate_out_reason():
    """ISSUE 15 satellite: /healthz responses carry a small JSON body —
    state (ok/draining/dead), queue depth, active count — so the router
    and any external LB get a rotate-out REASON, not just 200/503. The
    in-process ServingEngine.health() dict IS the HTTP body's detail."""
    import gc

    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
    from fleetx_tpu.serving import ServingEngine

    # engines are cyclic garbage (their jits close over self), so a
    # previous test's shut-down engine may still hold a draining probe
    # until the generational GC runs — force it so /healthz starts clean
    gc.collect()

    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    eng = ServingEngine(
        model, params, slots=2, cache_len=16, prefill_bucket=4,
        gen_cfg=GenerationConfig(decode_strategy="greedy",
                                 eos_token_id=10**6, pad_token_id=60,
                                 max_length=4))
    # model + capabilities joined the report in PR 18 (the model-aware
    # router's advertisement channel, docs/SERVING.md "Heterogeneous
    # fleet") — the load/rotate-out fields this test pins are unchanged
    assert eng.health() == {"state": "ok", "role": "both", "queue_depth": 0,
                            "queue_tokens": 0, "active": 0, "slots": 2,
                            "pages_in_use": 0, "usable_pages": 2,
                            "model": "gpt",
                            "capabilities": eng.capabilities.as_dict()}
    eng.submit(np.asarray([1, 2, 3], np.int32), max_length=4)
    assert eng.health()["queue_depth"] == 1
    srv = ObsServer(port=0).start()
    try:
        status, body = _get(srv.url + "/healthz")
        payload = json.loads(body)
        assert payload["state"] == "ok"
        assert payload["queue_depth"] == 1 and payload["active"] == 0
        detail = payload["detail"][eng._health_name]
        assert detail == eng.health()
        # draining: 503 with the REASON in the body
        eng.request_shutdown(grace_s=30.0)
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.url + "/healthz")
        assert exc.value.code == 503
        payload = json.loads(exc.value.read())
        assert payload["state"] == "draining"
        assert payload["detail"][eng._health_name]["state"] == "draining"
        # dead beats draining in the aggregate
        eng.declare_dead()
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.url + "/healthz")
        assert json.loads(exc.value.read())["state"] == "dead"
        # /snapshot mirrors the same health detail
        eng._dead = False
        eng._shutting_down = False
        _, body = _get(srv.url + "/snapshot")
        health = json.loads(body)["health"]
        assert health["state"] == "ok"
        assert health["detail"][eng._health_name]["state"] == "ok"
    finally:
        srv.stop()
        eng.shutdown(grace_s=0.0)
        unregister_health(eng._health_name)  # don't leak a 503 to later tests


def test_healthz_fails_after_recovery_exhausted():
    """A replica that died with RecoveryExhausted must report unhealthy —
    the router must stop routing to it even though it never drained."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
    from fleetx_tpu.obs.http import health_status
    from fleetx_tpu.resilience.faults import faults
    from fleetx_tpu.serving import RecoveryExhausted, ServingEngine

    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    eng = ServingEngine(
        model, params, slots=1, cache_len=16, prefill_bucket=4,
        max_recoveries=0,
        gen_cfg=GenerationConfig(decode_strategy="greedy",
                                 eos_token_id=10**6, pad_token_id=60,
                                 max_length=4))
    probe_name = eng._health_name
    eng.submit(np.asarray([1, 2, 3], np.int32), max_length=4)
    faults.configure(tick_raise="0+")
    try:
        with pytest.raises(RecoveryExhausted):
            for _ in range(10):
                eng.step()
    finally:
        faults.reset()
    ok, probes = health_status()
    assert probes[probe_name] is False, probes


# ------------------------------------------------------ trainer MFU line


def test_trainer_logs_no_mfu_on_cpu_and_sets_gauges(tmp_path, caplog):
    """The TRAIN ips: line carries the mfu field, the fleetx_train_*
    gauges are live, and on the CPU — a device utils/hw.py has no peak
    for — the field reads "-" and the gauge stays unset: a utilization
    against a placeholder peak is not a measurement."""
    import os
    import textwrap

    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.models import build_module
    from fleetx_tpu.obs import get_registry
    from fleetx_tpu.utils.config import get_config
    from fleetx_tpu.utils.log import logger

    yaml = textwrap.dedent("""
        Global:
          seed: 7
          local_batch_size: 2
          micro_batch_size: 2
        Engine:
          max_steps: 2
          logging_freq: 1
          eval_freq: 0
          eval_iters: 1
          save_load:
            save_steps: 1000
        Model:
          module: GPTModule
          vocab_size: 64
          hidden_size: 32
          num_layers: 1
          num_attention_heads: 2
          ffn_hidden_size: 64
          max_position_embeddings: 16
          hidden_dropout_prob: 0.0
          attention_probs_dropout_prob: 0.0
          use_flash_attention: False
        Optimizer:
          name: AdamW
          weight_decay: 0.01
          lr:
            name: CosineAnnealingWithWarmupDecay
            decay_steps: 100
            max_lr: 1.0e-3
            min_lr: 1.0e-4
    """)
    path = tmp_path / "cfg.yaml"
    path.write_text(yaml)
    cfg = get_config(str(path), nranks=1)
    cfg.Engine.save_load.output_dir = str(tmp_path / "out")
    rng = np.random.RandomState(0)
    gbs = cfg.Global.global_batch_size
    tokens = rng.randint(0, 64, (gbs, 16)).astype(np.int32)
    data = [{
        "tokens": tokens,
        "labels": ((tokens + 1) % 64).astype(np.int32),
        "loss_mask": np.ones((gbs, 16), np.float32),
    }] * 2
    trainer = Trainer(cfg, build_module(cfg))
    logger.propagate = True
    try:
        with caplog.at_level(logging.INFO, logger="fleetx_tpu"):
            trainer.fit(data)
    finally:
        logger.propagate = False
    train_lines = [r.message for r in caplog.records
                   if "ips_total" in r.message]
    assert train_lines, "no TRAIN ips: line logged"
    assert "mfu: -" in train_lines[-1], train_lines[-1]
    assert trainer._step_mfu(1.0) is None
    snap = get_registry().snapshot()
    assert snap["fleetx_train_steps_total"]["series"][0]["value"] >= 2
    assert snap["fleetx_train_tokens_per_second"]["series"][0]["value"] > 0
    assert not any(s["value"] for s in snap["fleetx_train_mfu"]["series"])
    assert snap["fleetx_train_step_seconds"]["series"][0]["count"] >= 2
