"""``--tiny`` rehearsals of every cell's control flow on the CPU (the
four-chip cell on virtual devices), and the data-driven promise: a
configuration, a traffic mix, a cell and a per-layer metric added as NEW
FILES, plus entries in BENCHMARK.json, are found without editing a file."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import harness

BENCH = harness.load_json("BENCHMARK.json")


def _run(root, *args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=harness.ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_tiny_rehearsal_runs_the_cells_control_flow(cell):
    result, out = _run(harness.ROOT, "--workload", cell, "--seed", "3",
                       "--seconds", "2", "--trace", "0", "--tiny")
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    # a rehearsal is never a measurement: no metric, never correct
    assert result["correct"] is False and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    wanted = {m["name"] for m in BENCH["end_to_end"]
              if cell in m.get("workloads", [cell])}
    assert set(result["rehearsal"]) == wanted
    assert '"compiles_in_window": 0' in out


def _listed(cell):
    return [m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])]


# What a ``--tiny --trace 1`` run of each cell reports on the CPU, where no
# reader of the device's trace, of its memory or of a peak finds anything:
# the names the parent's run (PR 36's tree) reported under a tag of each
# cell (``moe.`` / ``st.`` / ``lfm.``), written as the folded entries
# name them, and ``batch.tick_overlap_share``, which PR 37 added.
_CLOSED_LOOP = {"batch.lane_occupancy", "batch.tick_host_ms_p50",
                "batch.tick_ms_p50", "batch.tick_overlap_share"}
TINY_REPORTS = {
    "gpt345m-pretrain-s1024": {"train_data_wait_share"},
    "gpt1.3b-pretrain-dp2mp2": {"train_data_wait_share"},
    "gpt1.3b-serve-chat-steady-v3": {
        "chat.admit_gap_share", "chat.admit_host_ms_p50",
        "chat.lane_occupancy", "chat.tick_host_ms_p50", "chat.tick_ms_p50",
        "chat.tick_overlap_share", "gap_p99_ms", "gen_late_p99_ms",
        "queue_wait_p50_ms", "ttft_p90_ms"},
    "gpt1.3b-serve-docs-batch": _CLOSED_LOOP | {"batch.admit_host_ms_p50"},
    "olmoe-l8-serve-gen-batch": _CLOSED_LOOP | {
        "batch.admit_host_ms_p50", "moe_load_max_over_mean"},
    "smallthinker-l8-serve-longdoc-gen": _CLOSED_LOOP | {
        "moe_load_max_over_mean", "swa_pool_bytes_share",
        "swa_window_rows_share"},
    "lfm2-l14-serve-agent-prefix": _CLOSED_LOOP | {
        "batch.admit_host_ms_p50", "moe_load_max_over_mean",
        "prefix_tokens_saved_share", "state_bytes_share",
        "state_resume_share"},
}


@pytest.fixture(scope="module")
def traced_rehearsal():
    """The result of one ``--tiny --trace 1`` run a cell, made when a case
    first asks for it (the file runs on one worker); a run that failed is
    not made again for the cell's other cases."""
    results = {}

    def of(cell):
        if cell not in results:
            results[cell] = None
            results[cell], _ = _run(
                harness.ROOT, "--workload", cell, "--seed", "3",
                "--seconds", "2", "--trace", "1", "--tiny")
        return results[cell]
    return of


@pytest.mark.parametrize("cell,name", [
    (w["name"], name) for w in BENCH["workloads"]
    for name in _listed(w["name"])])
def test_traced_rehearsal_reports_a_shared_entry_in_each_cell_it_lists(
        traced_rehearsal, cell, name):
    """The fold's promise: a cell finds every entry that lists it, under
    the entry's one name, and reports what its readers can read."""
    result = traced_rehearsal(cell)
    assert result and result["attempted"] > 0 and result["failed"] == 0
    reported = set(result["rehearsal"])
    assert reported <= set(_listed(cell))
    assert (name in reported) == (name in TINY_REPORTS[cell]), sorted(reported)


def test_without_a_tpu_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout


def test_new_files_alone_add_a_config_a_mix_a_cell_and_a_metric(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    before = {}
    for directory, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            path = os.path.join(directory, f)
            before[path] = open(path, "rb").read()

    tiny_model = {"vocab_size": 256, "hidden_size": 32, "num_layers": 1,
                  "num_attention_heads": 2, "ffn_hidden_size": 64,
                  "max_position_embeddings": 128}
    new = {
        "perfbench/configs/throwaway.json": json.dumps({
            "source": "https://example.org/throwaway", "model": tiny_model,
            "compute_dtype": "float32"}),
        "perfbench/traffic/throwaway-mix.json": json.dumps({
            "driver": "serve_closed_loop", "clients": 2, "trace_s": 0.2,
            "tenants": [{"name": "t", "shared_prefix_len": 0,
                         "prompt": {"dist": "uniform", "min": 8, "max": 24},
                         "output": {"dist": "fixed", "value": 3}}]}),
        "perfbench/cells/throwaway-cell.json": json.dumps({
            "lanes": 2, "cache_len": 64, "page_size": 8, "pool_tokens": 128,
            "use_flash_attention": False}),
        "perfbench/layer_metrics/throwaway_ticks.py": (
            '"""Counts the program\'s tick spans."""\n\n\n'
            "def read(run):\n"
            "    return float(len(run.spans_named('serving.tick')))\n"),
    }
    for rel, text in new.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "throwaway", "source": "https://example.org/throwaway",
        "file": "perfbench/configs/throwaway.json", "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway-cell", "config": "throwaway",
        "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "probe.throwaway_ticks", "unit": "ticks", "better": "lower",
        "source": "program_span", "layer": "serving engine",
        "moves": "serve_tokens_per_s", "workloads": ["throwaway-cell"]})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("throwaway-cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    result, _ = _run(root, "--workload", "throwaway-cell", "--seed", "1",
                     "--seconds", "1", "--trace", "1", "--tiny")
    assert "probe.throwaway_ticks" in result["rehearsal"]
    assert result["attempted"] > 0 and result["failed"] == 0
    for path, content in before.items():  # nothing that was there changed
        assert open(path, "rb").read() == content, path
