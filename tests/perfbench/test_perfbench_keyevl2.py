"""The Keye-VL-2.0 configuration against the published one written out, its
parameter count against the program's own model AND tower, the cell and its
traffic (sessions of images between text rows), ``flops_dsa_gqa`` on
hand-worked cases, the readers of the new spans, scopes and kernel on
hand-made runs, the traced ``--tiny`` rehearsal of the new cell (whose
checks, at rehearsal size, pass the engine as built), and the driver's
reference check turned false by a fault planted in the indexer's seam."""

import itertools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import flops_dsa, flops_dsa_gqa, harness, probe_keyevl2
from perfbench.drivers import serve_closed_loop_vl as driver
from perfbench.layer_metrics import (_vl, gqa_selected_rows_share,
                                     gqa_sparse_decode_roofline,
                                     gqa_sparse_prefill_busy_share,
                                     gqa_sparse_prefill_roofline,
                                     image_rows_share, images_skipped_share,
                                     tower_busy_share)

CELL = "keyevl2-l6-serve-pagesqa-sparse"
BENCH = harness.load_json("BENCHMARK.json")
# Kwai-Keye/Keye-VL-2.0-30B-A3B, config.json (catalog architectures.jsonl),
# written out: the source's key and its value
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUT = {"num_hidden_layers": 6}
NEW = ["gqa_sparse_prefill_busy_share", "gqa_sparse_prefill_roofline",
       "tower_busy_share", "image_rows_share", "images_skipped_share",
       "gqa_sparse_decode_roofline", "gqa_selected_rows_share"]


def _config():
    entry = next(c for c in BENCH["configs"] if c["name"] == "keye-vl2-30b-l6")
    return entry, harness.load_json(entry["file"])


def test_every_width_is_the_published_one_and_only_the_depth_is_cut():
    entry, data = _config()
    model = data["model"]
    for key, value in PUBLISHED.items():
        assert data[key] == CUT.get(key, value), key
    assert sorted(entry["reduced"]) == sorted(data["reduced"]) == sorted(
        ["num_hidden_layers", "num_layers", "vision_num_hidden_layers"])
    assert data["published"] == {"num_hidden_layers": 48,
                                 "vision_num_hidden_layers": 27}
    # no width is cut, in the source's names or the program's
    assert not [k for k in data["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))]
    assert (model["hidden_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_size"]) == (
                2048, 32, 4, 128)
    assert (model["num_experts"], model["top_k"], model["ffn_hidden_size"],
            model["gate"], model["norm_topk_prob"]) == (
                128, 8, 768, "softmax_topk", True)
    assert (model["index_n_heads"], model["index_head_dim"],
            model["index_topk"]) == (16, 64, 2048)
    assert model["mrope_section"] == [16, 24, 24]
    assert model["index_rope_section"] == [8, 12, 12]
    assert model["rope_theta"] == 1e7 and model["vocab_size"] == 151936
    assert model["max_position_embeddings"] == 262144
    assert model["layer_types"] == ["full_attention"] * 6 == [
        "full_attention"] * model["num_layers"]
    tower = model["vision"]
    assert (tower["hidden_size"], tower["num_heads"],
            tower["intermediate_size"], tower["patch_size"], tower["grid"],
            tower["merge"]) == (1152, 16, 4304, 14, 27, 2)
    assert tower["num_layers"] == data["vision_num_hidden_layers"] == 4
    assert model["family"] == "keyevl2" and data["reference"] == "keyevl2_f32"
    assert "Eight pipeline stages" in data["deployment"]
    assert "embedding AND the head" in data["deployment"]
    assert any("FP8" in d for d in data["departures"])
    assert len(data["assumed"]) == 10
    assert any("SigLIP-so400m-patch14" in a for a in data["assumed"])
    assert data["source"] == entry["source"]


def test_the_parameter_count_is_the_programs_own_model_and_tower():
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
    from fleetx_tpu.models.vision.vit import tower_of

    _, data = _config()
    cfg = GPTConfig.from_model_config(data["model"])
    model = GPTForPretraining(cfg)

    def count(shapes):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))

    language = count(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"])
    tower = count(jax.eval_shape(lambda: tower_of(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((16, 588)),
        jnp.asarray([4, 4])))["params"])
    h = 2048
    attention = 2 * h * 4096 + 2 * h * 512
    indexer = h * 1024 + h * 64 + h * 16 + 128
    assert (attention, indexer) == (18_874_368, 2_261_120)
    layer = attention + 256 + indexer + 2 * h + h * 128 + 128 * 3 * h * 768
    assert layer == 625_381_760
    assert language == 6 * layer + 2 * 151_936 * h + h == 4_374_622_464
    block = (1152 * 3456 + 3456 + 1152 * 1152 + 1152 + 1152 * 4304 + 4304
             + 4304 * 1152 + 1152 + 2 * 2304)
    assert block == 15_239_504
    assert tower == (4 * block + 588 * 1152 + 1152 + 729 * 1152 + 2 * 2304
                     + 4608 * 4608 + 4608 + 4608 * h + h) == 93_158_464
    assert (language, tower) == (data["parameters_language"],
                                 data["parameters_tower"])
    assert language + tower == data["parameters"] == 4_467_780_928
    # a cached row: K 512 + V 512 + kI in its 128-lane tile, bfloat16
    assert (512 + 512 + 128) * 2 == 2304


def test_the_cell_and_its_traffic_are_the_issues():
    cell = harness.load_cell(CELL)
    job, deploy = cell.traffic, cell.deploy
    assert cell.chips == 1 and job["driver"] == "serve_closed_loop_vl"
    assert "clients" not in job and job["closed_loop"]["clients"] == 5
    assert (deploy["lanes"], deploy["cache_len"], deploy["page_size"],
            deploy["pool_tokens"], deploy["prefill_chunk"]) == (
                5, 33792, 16, 262144, 512)   # (6 lanes unless: it did)
    assert deploy["cache_len"] == 33 * 1024 >= 32768 + 256 + 384
    assert job["questions"] == 3 and job["document"] == {
        "dist": "lognormal", "median": 16384, "sigma": 0.35, "min": 8192,
        "max": 32768}
    assert job["image"] == {"side_min": 448, "side_max": 896,
                            "side_step": 28, "caption": 8}
    assert job["question"] == {"dist": "uniform", "min": 32, "max": 256}
    assert job["output"]["median"] == 192 and job["output"]["max"] == 384
    # the pool's bytes: three leaves a layer, 13,824 B a token
    pages = deploy["pool_tokens"] // 16 + 1
    assert pages * 6 * 16 * 1152 * 2 == pytest.approx(3.62e9, rel=0.01)


def test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved():
    (serve,) = [m for m in BENCH["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    assert serve["workloads"][-2:] == ["solar2-l8-serve-docreason-mixed",
                                       CELL]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == "keye-vl2-30b-l6"
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    for m in mine:
        assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
        assert m["moves"] in ("serve_tokens_per_s", "setup_s")
    assert [m["name"] for m in BENCH["per_layer"][-len(NEW):]] == NEW
    assert all(m["workloads"] == [CELL]
               for m in BENCH["per_layer"][-len(NEW):])
    listed = {m["name"] for m in mine}
    assert {"dsa_index_busy_share", "dsa_select_busy_share",
            "dsa_attn_busy_share", "moe_experts_roofline",
            "prefix_tokens_saved_share", "batch.tick_ms_p50"} <= listed
    # (they count a headless latent row: not read here)
    assert not {"dsa_decode_roofline", "dsa_prefill_roofline",
                "dsa_selected_rows_share", "mla_decode_roofline"} & listed
    assert len(BENCH["per_layer"]) <= 128 and len(BENCH["workloads"]) == 14
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


# ------------------------------------------------------------- the stream

def _head(cell, seed, client, n):
    model = cell.config["model"]
    return list(itertools.islice(driver.client_stream(
        cell.traffic, seed, client, model["vocab_size"],
        group=model["vision"]), n))


@pytest.mark.parametrize("tiny", [False, True])
def test_the_stream_is_a_function_of_the_seed_for_tokens_and_pixels_alone(
        tiny):
    cell = harness.load_cell(CELL, tiny=tiny)
    n = 2 if not tiny else 5
    a, b, c = (_head(cell, seed, 1, n) for seed in (7, 7, 2 ** 31 + 11))
    sizes = lambda rs: [(len(r.prompt), r.max_new_tokens, r.tenant,  # noqa: E731
                         [i.shape for i in r.images]) for r in rs]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(np.array_equal(i, j) for x, y in zip(a, b)
               for i, j in zip(x.images, y.images))
    assert sizes(a) == sizes(c)           # the seed draws no length, no grid
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    assert not np.array_equal(a[0].images[0], c[0].images[0])
    token = cell.config["model"]["vision"]["image_token_id"]
    for r in a:
        text = r.prompt[r.prompt != token]
        assert 1 <= text.min() and text.max() < token


def test_a_session_is_nine_parts_in_ten_rows_of_images_and_fits_the_cache():
    cell = harness.load_cell(CELL)
    token = cell.config["model"]["vision"]["image_token_id"]
    for client in range(3):
        first = 3 - client % 3
        requests = _head(cell, 5, client, first + 1)
        assert [r.tenant for r in requests] == [
            f"doc0.q{q}" for q in range(3 - first, 3)] + ["doc1.q0"]
        for r in requests:
            assert len(r.prompt) + r.max_new_tokens <= cell.deploy["cache_len"]
            marked = int((r.prompt == token).sum())
            session = len(r.prompt) - 256
            assert 8192 <= session and session % 16 in range(16)
            assert marked / len(r.prompt) > 0.85
            assert 8 <= len(r.images) <= 72
            rows = [(i.shape[0] // 28) * (i.shape[1] // 28) for i in r.images]
            assert sum(rows) == marked
            assert all(i.shape[0] % 28 == 0 and 224 <= i.shape[0] <= 896
                       and i.shape[1] % 28 == 0 and i.dtype == np.uint8
                       for i in r.images)
            # all but the last are 448-896 a side: 1,024-4,096 patches
            assert all(256 <= n <= 1024 for n in rows[:-1])
        # the questions of one session share its rows and its images
        if first > 1:
            a, b = requests[0], requests[1]
            assert a.images is b.images
            assert np.array_equal(a.prompt[:8192], b.prompt[:8192])


# ------------------------------------------------ operations and bytes

def test_flops_dsa_gqa_on_hand_worked_cases():
    model = harness.load_cell(CELL).config["model"]
    assert flops_dsa_gqa.widths(model) == (32, 4, 128)
    # the indexer: 16 heads x 64 a pair, a 128 B key
    assert flops_dsa.index_cost(1, model) == (2.0 * 16 * 64, 128.0)
    # a chunk's pair: 32 heads x (128 + 128) x 2
    assert flops_dsa_gqa.sparse_chunk_cost(512 * 2048, model) == (
        512 * 2048 * 16384.0, 0.0)


# ----------------------------------------------------------- the readers

def _span(name, start, **attrs):
    return types.SimpleNamespace(name=name, start_s=start, end_s=start + 0.01,
                                 attrs=attrs)


def _run(spans=(), trace=None, traced=None):
    run = types.SimpleNamespace(
        spans=list(spans), counters={}, window=(0.0, 10.0), trace=trace,
        traced=traced, peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        cell=harness.load_cell(CELL))
    run.spans_named = lambda name: [s for s in run.spans if s.name == name]
    return run


READERS = (gqa_sparse_prefill_busy_share, gqa_sparse_prefill_roofline,
           tower_busy_share, image_rows_share, images_skipped_share,
           gqa_sparse_decode_roofline, gqa_selected_rows_share)


def test_a_program_without_the_spans_scopes_or_kernel_reports_nothing():
    """What the parent commit's program gives the new readers: no field on
    its spans, no scope in its trace; none raises and each leaves its
    metric out."""
    bare = _run([_span("serving.decode", 1.0, batch=3, attn_rows=900),
                 _span("serving.admit", 2.0, prompt_len=9, matched=0)])
    for reader in READERS:
        assert reader.read(bare) is None
    traced = _run(bare.spans, trace={"busy_s": 1.0, "family_calls": {},
                                     "family_s": {}}, traced=(0.0, 3.0))
    for reader in READERS:   # no trace file either, no field
        assert reader.read(traced) is None
    rows = [["%fusion.1 = ...", "jit(f)/layer/attn/attn_full/dot", "jit_f", 0,
             100]]
    read = _vl.seconds_of({"/device:TPU:0": rows})
    assert read["total"] > 0 and not read["tower"] and not read["kernel_calls"]


def test_span_fields_scopes_and_the_kernel_on_hand_made_rows(monkeypatch):
    spans = [_span("serving.decode", 1.0, attn_rows=120_000,
                   selected_rows=12_288, index_rows=120_000),
             _span("serving.decode", 2.0, attn_rows=130_000,
                   selected_rows=12_288, index_rows=130_000),
             _span("serving.prefill_chunk", 1.5, selected_rows=512 * 2048),
             _span("serving.admit", 1.7, prompt_len=256, matched=0,
                   selected_rows=256 * 2048),
             _span("serving.admit", 1.8, prompt_len=16640, matched=16384,
                   images=28, image_rows=14800, images_skipped=28),
             _span("serving.admit", 3.0, prompt_len=16384, matched=0,
                   images=28, image_rows=14900, images_skipped=0),
             _span("serving.admit", 11.0, prompt_len=1, matched=0, images=1,
                   image_rows=1, images_skipped=1)]
    run = _run(spans, trace={"busy_s": 1.0, "family_calls": {"decode": 12},
                             "family_s": {"decode": 12 * 60e-6}},
               traced=(0.5, 2.2))
    assert image_rows_share.read(run) == pytest.approx(29700 / 33024)
    assert images_skipped_share.read(run) == pytest.approx(0.5)
    scope = "jit(f)/cached_forward/_decoder_stack/while/body/layer/attn/"
    rows = [  # [instruction text, op_name, program, start_ns, dur_ns]
        ["%fusion.1 = ...", scope + "dsa_index/dot", "jit_f", 0, 100],
        ["%fleetx_gqa_sparse_prefill.2 = custom-call", scope + "dsa_attn/x",
         "jit_f", 100, 300],
        ["%fusion.3 = ...", "jit(run)/tower/vit_attn/dot", "jit_run", 400,
         200],
        ["%fusion.4 = ...", "jit(run)/tower/vit_project/dot", "jit_run", 600,
         100],
        ["%fusion.5 = ...", "jit(run)/tower/convert", "jit_run", 700, 100],
        ["%fusion.6 = ...", scope + "mlp/dot", "jit_f", 800, 200]]
    read = _vl.seconds_of({"/device:TPU:0": rows})
    assert read["total"] == pytest.approx(1e-6)
    assert read["tower"] == pytest.approx(0.4e-6)
    assert (read["kernel"], read["kernel_calls"]) == (pytest.approx(0.3e-6), 1)
    monkeypatch.setattr(_vl, "seconds", lambda run: read)
    assert tower_busy_share.read(run) == pytest.approx(0.4)
    assert gqa_sparse_prefill_busy_share.read(run) == pytest.approx(0.3)
    # 12 calls (6 layers x 2 prefill programs) of (512 + 256) / 2 x 2,048
    # pairs at the mean: 32 x 256 x 2 operations a pair at the chip's peak
    read.update(kernel=12 * 10e-3, kernel_calls=12)
    least = 384 * 2048 * 16384 / 197e12
    assert gqa_sparse_prefill_roofline.read(run) == pytest.approx(
        100 * least / 10e-3)
    assert gqa_sparse_prefill_roofline.read(run) < 100


def test_a_ticks_attention_is_its_gather_and_its_kernel(monkeypatch):
    """``gqa_sparse_decode_roofline`` times what a tick spends under
    ``dsa_attn``: the gather of the chosen rows (the one read of HBM) with
    the kernel over the compact pool, in the programs that run the kernel
    and in no other; and ``gqa_selected_rows_share`` is the spans' own."""
    spans = [_span("serving.decode", 1.0, index_rows=100_000,
                   selected_rows=10_240),
             _span("serving.decode", 2.0, index_rows=140_000,
                   selected_rows=10_240),
             _span("serving.decode", 9.0, index_rows=1, selected_rows=1)]
    run = _run(spans, trace={"busy_s": 1.0, "family_calls": {},
                             "family_s": {}}, traced=(0.5, 2.2))
    assert gqa_selected_rows_share.read(run) == pytest.approx(20_480 / 240_000)
    tick = "jit(_decode_fn)/cached_forward/_decoder_stack/while/body/layer/"
    chunk = "jit(prefill)/cached_forward/_decoder_stack/while/body/layer/"
    rows = [  # [instruction text, op_name, program, start_ns, dur_ns]
        ["%fusion.1 = ...", tick + "attn/dsa_attn/gather", "jit__decode_fn",
         0, 350],
        ["%fleetx_decode_paged.1 = custom-call",
         tick + "attn/dsa_attn/fleetx_decode_paged/pallas_call",
         "jit__decode_fn", 350, 25],
        ["%fusion.2 = ...", tick + "attn/dsa_select/sort", "jit__decode_fn",
         375, 200],
        ["%fusion.3 = ...", chunk + "attn/dsa_attn/gather", "jit_prefill",
         575, 400],
        ["%fleetx_gqa_sparse_prefill.2 = custom-call",
         chunk + "attn/dsa_attn/x", "jit_prefill", 975, 25]]
    read = _vl.seconds_of({"/device:TPU:0": rows})
    assert read["tick_attn"] == pytest.approx(375e-9)
    assert read["tick_calls"] == 1 and read["kernel_calls"] == 1
    assert read["total"] == pytest.approx(1e-6)
    monkeypatch.setattr(_vl, "seconds", lambda run: read)
    # 12 calls of 10,240 rows x 2,048 B at the chip's bandwidth: 25.6 us
    read.update(tick_attn=12 * 375e-6, tick_calls=12)
    assert flops_dsa_gqa.sparse_decode_cost(10_240, run.cell.config["model"]
                                            ) == (10_240 * 16384.0,
                                                  10_240 * 2048.0)
    assert gqa_sparse_decode_roofline.read(run) == pytest.approx(
        100 * (10_240 * 2048 / 819e9) / 375e-6)
    # the kernel's events alone (27 us a call) would read 95%, and above 100
    # at the speed the compact pool is read at: what the reader must not do
    read.update(tick_attn=0.0, tick_calls=0)
    assert gqa_sparse_decode_roofline.read(run) is None


# --------------------------------------------------- the traced rehearsal

def _listed():
    return [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [CELL])]


# what a ``--tiny --trace 1`` run reports on the CPU, where no reader of
# the device's trace, of its memory or of a peak finds anything
TINY_REPORTS = {"batch.lane_occupancy",
                "batch.tick_host_ms_p50", "batch.tick_ms_p50",
                "batch.tick_overlap_share", "moe_load_max_over_mean",
                "prefix_tokens_saved_share", "image_rows_share",
                "images_skipped_share", "gqa_selected_rows_share"}
# (reported where the two seconds held a sampled admission: either way)
SOMETIMES = {"batch.admit_host_ms_p50"}


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
    assert reported <= set(_listed())
    assert name in SOMETIMES or (
        (name in reported) == (name in TINY_REPORTS)), sorted(reported)
    assert '"compiles_in_window": 0' in out and '"engine_ok": true' in out
    assert '"reference_ok": true' in out and '"selection_ok": true' in out
    assert '"images_ok": true' in out and "'images_skipped'" in out
    assert '"engine_tokens_served_checked": 6' in out   # 3 lanes x 2: FIXED


# ------------------------------------------ the checks at rehearsal size

@pytest.fixture(scope="module")
def tiny_engine():
    cell = harness.load_cell(CELL, tiny=True)
    model, variables = driver.build_model(cell, 11)
    engine = driver.build_engine(cell, model, variables)
    return cell, model, variables, engine


def test_the_models_norm_weights_are_drawn_off_one(tiny_engine):
    import jax

    _, _, variables, _ = tiny_engine
    scales = [np.asarray(leaf, np.float32) for path, leaf in
              jax.tree_util.tree_flatten_with_path(variables["params"])[0]
              if path[-1].key == "scale"]
    assert len(scales) >= 10 and "vision" in variables["params"]
    assert all(0.02 < np.abs(s - 1).mean() < 0.3 for s in scales)


def test_a_planted_fault_turns_the_reference_check_false(tiny_engine,
                                                         monkeypatch):
    """The indexer's ReLU left out (the seam ``indexer._index_act``, which
    the grouped attention's scores pass through): the selection moves, and
    the check says so; as built it passes."""
    from fleetx_tpu.models.gpt import indexer

    cell, _, variables, engine = tiny_engine
    good = driver.reference_check(engine, variables, cell, 11)
    assert good["reference_ok"] and good["images_ok"], good
    assert good["tower_images_checked"] == 7
    assert good["hit_images_encoded"] == 0 and good["cold_images_encoded"] == 7
    monkeypatch.setattr(indexer, "_index_act", lambda dots: dots)
    bad = driver.reference_check(engine, variables, cell, 11,
                                 driver.Served(engine))
    assert not bad["selection_ok"] and not bad["reference_ok"]


@pytest.fixture(scope="module")
def tiny_served(tiny_engine):
    return driver.Served(tiny_engine[3])


def test_the_engine_check_runs_the_engines_programs_cold(tiny_engine,
                                                         tiny_served):
    """As built the engine's own programs are ``Served``'s to float32's
    rounding, on a FIXED count of tokens, every image encoded by the
    ENGINE'S tower programs whatever the trie holds."""
    cell, _, _, engine = tiny_engine
    for _ in range(2):   # (the second finds the first's document registered)
        good = driver.engine_check(engine, tiny_served, 1.0, cell, 11)
        assert good["engine_ok"], good
        assert good["engine_images_encoded"] == 3 * 7
        assert good["engine_tokens_served_checked"] == 3 * 2
        assert good["engine_rows_max_rel_rms_err"] < 1e-4


@pytest.mark.parametrize("fault", probe_keyevl2.ENGINE_FAULTS)
def test_a_fault_in_what_the_engines_programs_are_handed_is_refused(
        tiny_engine, tiny_served, fault):
    """``probe_keyevl2.engine_planted``: the lane install's eleventh int,
    a chunk's three position rows, the stage's place: ``Served`` runs none
    of them, so ``engine_check`` is what refuses each."""
    cell, _, _, engine = tiny_engine
    with probe_keyevl2.engine_planted(engine, fault):
        bad = driver.engine_check(engine, tiny_served, 1.0, cell, 11)
    assert not bad["engine_ok"], bad
    assert bad["engine_rows_max_rel_rms_err"] > driver.ENGINE_ROWS_TOL
    assert bad["engine_images_encoded"] == 3 * 7
    # and the hook is gone
    assert not {"_install_lane", "_prefill_args",
                "_guarded_prefill"} & set(vars(engine))
