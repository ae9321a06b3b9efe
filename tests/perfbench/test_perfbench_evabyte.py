"""The EvaByte configuration against the published one written out, its
parameter count against the program's own tree, the cell's bytes, the stream
(lengths of ``order_seed`` and the client alone, ids of the seed), the five
new readers on recorded and hand-made runs, ``flops_eva.py`` against a hand
count, the traced ``--tiny`` rehearsal of the new cell (whose checks, at
rehearsal size, pass the engine as built), and the driver's reference check
at rehearsal size: it passes the engine as built, and the faults that
``perfbench/probe_evabyte.py`` plants (which puts the same questions on the
chip at the published widths) turn it false."""

import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import flops_eva, harness, probe_evabyte, traffic
from perfbench.drivers import serve_closed_loop_eva as driver
from perfbench.layer_metrics import (_scope, eva_decode_roofline,
                                     eva_pool_busy_share,
                                     eva_rows_read_share,
                                     eva_summary_bytes_share,
                                     eva_summary_rows_share)

CELL = "evabyte-l8-serve-bytedocs-longctx"
CONFIG = "evabyte-6.5b-pp4-l8"
BENCH = harness.load_json("BENCHMARK.json")
NEW = ["eva_decode_roofline", "eva_pool_busy_share", "eva_summary_rows_share",
       "eva_rows_read_share", "eva_summary_bytes_share"]

# the catalog row's ``config`` (architectures.jsonl beside the model-configs
# guide, ``EvaByte``), written out
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}


def _config():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    return entry, harness.load_json(entry["file"])


def test_every_width_is_the_published_one_and_the_cut_is_of_depth_alone():
    entry, data = _config()
    assert entry["reduced"] == data["reduced"] == ["num_hidden_layers"]
    assert data["published"] == {"num_hidden_layers": 32}
    for key, value in PUBLISHED.items():
        assert data[key] == (8 if key == "num_hidden_layers" else value), key
    model = data["model"]
    assert (model["hidden_size"], model["num_attention_heads"],
            model["ffn_hidden_size"], model["vocab_size"]) == (
        4096, 32, 11008, 320)
    assert (model["eva_chunk_size"], model["eva_window_size"],
            model["num_pred_heads"], model["num_layers"]) == (16, 2048, 8, 8)
    assert model["residual_dtype"] == "float32" and model["norm_unit_offset"]
    assert model["rope_theta"] == 100000.0 and model["norm_eps"] == 1e-05
    assert len(data["departures"]) == 2
    assert "self-speculative" in data["departures"][0]
    assert "prefix reuse" in data["departures"][1]
    for letter in "abcdefgh":
        (reading,) = [a for a in data["assumed"]
                      if a.startswith(f"({letter})")]
        assert "OTHER READING" in reading, letter
    assert "four pipeline stages" in data["deployment"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_the_yaml_carries_the_same_model_section():
    from fleetx_tpu.utils.config import get_config

    _, data = _config()
    published = get_config(os.path.join(harness.ROOT, data["train_yaml"]),
                           nranks=1, overrides=["Distributed.dp_degree=1"]).Model
    for key, value in data["model"].items():
        assert published.get(key) == value, key


def test_the_parameter_tree_is_the_configurations_count():
    """The program's own tree at the published widths (shapes alone), and
    the issue's arithmetic term by term."""
    import flax
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    _, data = _config()
    model = GPTForPretraining(GPTConfig.from_model_config(
        dict(data["model"], fuse_attn_qkv=True)))
    shapes = jax.eval_shape(lambda: flax.core.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))["params"]
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == 202_391_552
    rest = 320 * 4096 + 4096 * 8 * 320 + 4096
    assert count == 8 * layer + rest == data["parameters"] == 1_630_932_992
    assert 32 * layer + rest == 6_488_330_240
    assert shapes["lm_head"].shape == (2560, 4096)
    attn = shapes["gpt"]["layers"]["layer"]["attn"]
    assert attn["eva_mu"].shape == attn["eva_phi"].shape == (8, 32, 128)


def test_the_cell_and_its_traffic_are_the_issues():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    deploy, job = cell.deploy, cell.traffic
    assert deploy["lanes"] == job["clients"]
    assert (deploy["cache_len"], deploy["page_size"],
            deploy["prefill_chunk"], deploy["prefill_bucket"]) == (
        30208, 16, 512, 256)
    assert deploy["cache_len"] == 28672 + 1536 <= 32768
    assert not deploy["cache_len"] % 512 and not 2048 % deploy["prefill_chunk"]
    (tenant,) = job["tenants"]
    assert tenant["prompt"] == {"dist": "lognormal", "median": 12288,
                                "sigma": 0.5, "min": 4096, "max": 28672}
    assert tenant["output"] == {"dist": "lognormal", "median": 768,
                                "sigma": 0.4, "min": 256, "max": 1536}
    assert job["block"] == 4 and job["trace_s"] == 3.0
    assert job["driver"] == "serve_closed_loop_eva"
    # the bytes: both classes over 8 layers, 16,384 B a row and layer
    row = flops_eva.row_bytes(cell.config["model"])
    assert row == 16384
    lanes, page = deploy["lanes"], deploy["page_size"]
    window = (lanes * 128 + 1) * page * row * 8
    summary = (deploy["pool_tokens"] // page + 1) * page * row * 8
    assert deploy["pool_tokens"] // page == {24: 1728, 16: 1152}[lanes]
    assert window == pytest.approx({24: 6.44e9, 16: 4.30e9}[lanes], rel=0.01)
    assert summary == pytest.approx({24: 3.63e9, 16: 2.42e9}[lanes], rel=0.01)
    weights = 2 * cell.config["parameters"]
    assert weights + window + summary < 15.75e9
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert len(entry["why"]) <= 200 and entry["traffic"] == "bytedocs-longctx"


def test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved():
    (serve,) = [m for m in BENCH["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    assert serve["workloads"][-2:] == ["ling3-l7-serve-reason-widebatch",
                                       CELL]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == CONFIG
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    for m in mine:
        assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
        assert m["moves"] in ("serve_tokens_per_s", "setup_s")
    names = {m["name"] for m in mine}
    assert set(NEW) | {
        "batch.tick_ms_p50", "batch.prefill_ms_p50", "batch.lane_occupancy",
        "batch.decode_kernel_device_share", "batch.hbm_peak_gb",
        "batch.attn_device_share", "setup_compile_s", "setup_programs",
        "setup_trace_lower_s", "setup_cache_load_s"} <= names
    # the rooflines that count positions or the sliding classes' rows, and
    # the sliding classes' pool share, do not read this cell truthfully
    assert not names & {"batch.decode_paged_roofline", "swa_decode_roofline",
                        "swa_pool_bytes_share", "swa_window_rows_share",
                        "prefill_gqa_roofline"}
    new = BENCH["per_layer"][-5:]
    assert [m["name"] for m in new] == NEW
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "serve_tokens_per_s" for m in new)
    assert [m["source"] for m in new] == [
        "device_trace", "device_trace", "program_span", "program_span",
        "program_counter"]
    assert [m["layer"] for m in new] == [
        "kernels", "model", "scheduler and cache", "scheduler and cache",
        "scheduler and cache"]
    assert new[0]["unit"] == "%"
    assert len(BENCH["per_layer"]) == 119 and len(BENCH["workloads"]) == 16
    assert len(BENCH["configs"]) == 14
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(json.dumps(BENCH, indent=1)) < 65536


# ------------------------------------------------------------- the stream

def _head(job, seed, client, n, vocab=320):
    return list(itertools.islice(
        traffic.client_stream(job, seed, client, vocab), n))


@pytest.mark.parametrize("tiny", [False, True])
def test_lengths_are_of_order_seed_and_the_client_and_ids_of_the_seed(tiny):
    job = harness.load_cell(CELL, tiny=tiny).traffic
    a, b = _head(job, 11, 3, 8), _head(job, 12, 3, 8)
    assert [(len(r.prompt), r.max_new_tokens) for r in a] == [
        (len(r.prompt), r.max_new_tokens) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    again = _head(job, 11, 3, 8)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, again))
    other = _head(job, 11, 4, 8)
    assert [len(r.prompt) for r in other] != [len(r.prompt) for r in a]
    moved = _head(dict(job, order_seed=job["order_seed"] + 1), 11, 3, 8)
    assert [len(r.prompt) for r in moved] != [len(r.prompt) for r in a]
    assert all(0 <= int(r.prompt.min()) and int(r.prompt.max()) < 320
               for r in a)


def test_every_prompt_of_the_stream_leaves_a_chunk_open():
    """The block's four quantiles of the prompt's length, none a multiple of
    16: every prompt leaves an open chunk for the ticks to close."""
    job = harness.load_cell(CELL).traffic
    lengths = {len(r.prompt) for c in range(4) for r in _head(job, 1, c, 8)}
    assert lengths == {6913, 10478, 14410, 21841}
    assert all(n % 16 for n in lengths)


# ------------------------------------------------------------- the readers

def _span(name, start, **attrs):
    import types

    return types.SimpleNamespace(name=name, start_s=start, attrs=attrs)


def _run(trace=None, counters=None, spans=(), peaks=None):
    return harness.Run(
        cell=harness.load_cell(CELL), device={}, setup_s=1.0,
        window=(0.0, 40.0), attempted=1, failed=0, correct=True, checks={},
        samples={"lanes": 24}, spans=list(spans), counters=counters or {},
        traced=(30.0, 34.0) if trace else None, trace=trace, peaks=peaks)


def test_flops_eva_against_a_hand_count():
    model = harness.load_cell(CELL).config["model"]
    ops, bytes_ = flops_eva.decode_tick_cost(24_000, 19_200, 24, model)
    rows = (24_000 + 19_200) * 8
    assert bytes_ == rows * 16384 + 2 * 24 * 4096 * 2 * 8
    assert ops == 4 * rows * 4096
    # memory-bound on a v5e: 5.66 GB at 819 GB/s is 6.9 ms
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    from perfbench import flops

    seconds, bound = flops.roofline_seconds(ops, bytes_, peaks)
    assert bound == "memory" and seconds == pytest.approx(6.91e-3, rel=0.01)


def test_a_program_without_the_fields_or_the_counters_reports_nothing():
    # an untraced run, and a parent commit's program (no such field, counter)
    parent = [_span("serving.decode", 31.0, batch=24, full_rows=9)]
    for reader in (eva_decode_roofline, eva_pool_busy_share,
                   eva_summary_rows_share, eva_rows_read_share,
                   eva_summary_bytes_share):
        assert reader.read(_run()) is None
        assert reader.read(_run(spans=parent, counters={
            "pages_in_use_full": 4, "pages_in_use_window": 2})) is None
    trace = {"family_calls": {"decode": 800}, "family_s": {"decode": 1.0}}
    assert eva_decode_roofline.read(_run(trace=trace, spans=parent, peaks={
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})) is None


def test_the_span_and_counter_readers_on_a_recorded_run():
    """Two ticks inside the traced stretch and one outside it, and the pool's
    counters of my first chip run, PR 66
    (chiprun_out/pr66/l24_run_6600000011.log)."""
    spans = [_span("serving.decode", 31.0, eva_window_rows=24_000,
                   eva_summary_rows=20_000, eva_positions=352_000),
             _span("serving.decode", 32.0, eva_window_rows=26_000,
                   eva_summary_rows=18_000, eva_positions=352_000),
             _span("serving.decode", 5.0, eva_window_rows=10_000,
                   eva_summary_rows=0, eva_positions=10_000),
             _span("serving.prefill_chunk", 31.5, eva_window_rows=512)]
    run = _run(spans=spans, counters={"pages_in_use_summary": 1377,
                                      "pages_in_use_window": 1556})
    assert eva_summary_rows_share.read(run) == pytest.approx(38 / 98)
    assert eva_rows_read_share.read(run) == pytest.approx(98 / 714)
    assert eva_summary_bytes_share.read(run) == pytest.approx(
        1377 / 2933) == pytest.approx(0.4695, abs=1e-4)
    # the roofline: the traced stretch's two ticks, 16 kernel calls
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"family_calls": {"decode": 16}, "family_s": {"decode": 0.020}}
    model = run.cell.config["model"]
    _, bytes_ = flops_eva.decode_tick_cost(25_000, 19_000, 24, model)
    got = eva_decode_roofline.read(_run(trace=trace, spans=spans,
                                        peaks=peaks))
    assert got == pytest.approx(100 * 2 * bytes_ / 819e9 / 0.020)
    assert 60 < got < 80


def test_the_scope_reader_on_hand_made_rows(monkeypatch):
    from perfbench.layer_metrics import _parts

    path = ("jit(_decode_fn)/cached_forward/GPTModel/layers/"
            "layers._decoder_stack/while/body/layer/attn/")
    rows = [["fusion.1", path + "eva_pool/softmax/exp", 0, 0, 300],
            ["fusion.2", path + "attn_window/dot_general", 0, 1000, 1500],
            ["fusion.3", path + "eva_pool/cache_write/scatter", 0, 3000, 200],
            ["fusion.4", path + "eva_pooling/dot", 0, 4000, 2000]]
    monkeypatch.setattr(_parts, "_named", lambda rows: rows)
    assert _scope.share_of({0: rows}, "eva_pool") == pytest.approx(500 / 4000)
    assert eva_pool_busy_share.read(_run()) is None


# --------------------------------------------------- the traced rehearsal

def _listed():
    return [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [CELL])]


# what a ``--tiny --trace 1`` run reports on the CPU, where no reader of
# the device's trace, of its memory or of a peak finds anything
TINY_REPORTS = {"batch.lane_occupancy", "batch.tick_host_ms_p50",
                "batch.tick_ms_p50", "batch.tick_overlap_share",
                "eva_summary_rows_share", "eva_rows_read_share",
                "eva_summary_bytes_share"}


@pytest.fixture(scope="module")
def traced_rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=harness.ROOT)
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 5), "--seconds", "2",
         "--trace", "1", "--tiny"], cwd=harness.ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("name", _listed())
def test_traced_rehearsal_reports_each_entry_that_lists_the_cell(
        traced_rehearsal, name):
    result, out = traced_rehearsal
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is False and result["metrics"] == {}
    reported = set(result["rehearsal"])
    assert reported <= set(_listed()) | {"serve_tokens_per_s", "setup_s"}
    assert (name in reported) == (name in TINY_REPORTS), sorted(reported)
    assert '"compiles_in_window": 0' in out and '"engine_ok": true' in out
    assert '"reference_ok": true' in out
    assert '"eva_windows_tumbled"' in out and '"eva_chunks_closed"' in out


def test_the_tiny_cell_runs_end_to_end_and_is_correct():
    """The driver itself on the rehearsal cell (``run.py --tiny`` never
    reports a rehearsal as correct: it is no measurement): the loop, both
    checks and the decision, with the kernels' presence waived off the
    chip."""
    import time

    cell = harness.load_cell(CELL, tiny=True)
    run = driver.run(cell, seed=2 ** 31 + 9, seconds=2.0, trace=False,
                     t_process=time.perf_counter())
    assert run.correct and run.checks["correct"], run.checks
    assert run.attempted > 0 and run.failed == 0
    assert run.checks["reference_ok"] and run.checks["engine_ok"]
    assert run.checks["engine_lanes_checked"] >= 1
    assert run.checks["reference_heads_checked"] == 8
    assert run.checks["windows_tumbled_in_check"] >= 3
    spans = [s.attrs for s in run.spans if s.name == "serving.decode"]
    assert spans and all("eva_window_rows" in a and "eva_positions" in a
                         for a in spans)
    assert run.counters["eva_chunks_closed"] > 0
    assert run.counters["eva_windows_tumbled"] > 0
    assert 0 < eva_summary_bytes_share.read(run) < 1
    assert 0 < eva_rows_read_share.read(run) < 1


# ------------------------------------------ the checks at rehearsal size

# the faults of the two classes' LIFECYCLES (each reading builds an engine and
# retraces the check's programs); ``python perfbench/probe_evabyte.py --seeds 7
# --tiny`` plants all nine here and every one but ``bf16_residual`` reads not
# correct (a rehearsal computes in float32, so the stream in the compute dtype
# IS the system as built there: that one is the chip's to read, PERF.md)
PLANTED = ("own_window_visible", "window_slides", "padded_rows_pooled",
           "open_chunk_dropped")


@pytest.fixture(scope="module")
def readings():
    cell = harness.load_cell(CELL, tiny=True)
    return dict(probe_evabyte.readings(cell, driver, 11,
                                       only=("as_built", *PLANTED)))


def test_the_reference_check_passes_the_engine_as_built(readings):
    out = readings["as_built"]
    assert out["reference_ok"], out
    assert out["reference_positions_checked"] == 12 + 16
    assert out["reference_rms_err"] < 1e-5 * out["reference_logit_std"]
    assert out["pooled_row_max_rel_err"] < 1e-5


@pytest.mark.parametrize("fault", PLANTED)
def test_a_planted_fault_turns_the_reference_check_false(readings, fault):
    assert len(probe_evabyte.FAULTS) == 9 and set(PLANTED) <= set(
        probe_evabyte.FAULTS)
    out = readings[fault]
    assert not out["reference_ok"], out
