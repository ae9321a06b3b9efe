"""Test harness: force an 8-device virtual CPU platform so every parallelism
strategy (dp/fsdp/mp/pp/sp/ep collectives) is exercised without a TPU —
the unit-test pyramid the reference lacks (SURVEY.md §4).

FLEETX_TEST_PLATFORM=real skips the CPU pin so a test file runs against the
attached accelerator — the on-chip kernel certification (README
"Benchmarks"): without this escape hatch the pin rehomes every kernel
test onto the virtual CPU platform and the TPU-gated ``_on_tpu()`` tests
never run anywhere.
"""

import os
import subprocess

_REAL = os.environ.get("FLEETX_TEST_PLATFORM") == "real"

if not _REAL:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("FLEETX_LOG_LEVEL", "WARNING")

import jax  # noqa: E402
import pytest  # noqa: E402

if _REAL:
    # a TPU's default f32 matmul is one bf16 pass: the XLA references the
    # kernel files compare against must stay f32-exact on the chip too
    jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    """Register the suite's markers (no pytest.ini in this repo).

    ``chaos`` — deterministic fault-injection resilience tests
    (tests/test_resilience.py). They run on CPU in seconds and stay
    INSIDE the tier-1 ``-m 'not slow'`` selection by design: resilience
    regressions should fail the same gate as correctness regressions.
    ``slow`` — opt-out marker the tier-1 selection excludes."""
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection resilience test "
        "(fast, CPU, part of tier-1)")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 'not slow' selection")


# Parametrised cases that cannot apply to the entry they were generated
# for, skipped by name with the reason (the test files are not edited).
_CANNOT_APPLY = {
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/olmoe-1b-7b-l8.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/perfbench/test_perfbench_olmoe.py"
        " holds this configuration's count to the program's own model",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/smallthinker-21b-a3b-l8.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/test_smallthinker_serving.py "
        "holds this configuration's count to the program's own model",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/lfm2-8b-a1b-l14.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/test_lfm2_serving.py "
        "holds this configuration's count to the program's own model",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/jamba2-3b.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/test_jamba2_serving.py "
        "holds this configuration's count to the program's own model",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/axk1-ep16-l6.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/perfbench/test_perfbench_axk1.py"
        " holds this configuration's count to the program's own model",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/dsv32-ep16-l5.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/perfbench/test_perfbench_dsv32.py"
        " holds this configuration's count to the program's own model",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/trinity-large-ep8-l5.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/perfbench/"
        "test_perfbench_trinity.py holds this configuration's count to the "
        "program's own model",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/longcat-flash-ep32-l4.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/perfbench/"
        "test_perfbench_longcat.py holds this configuration's count to the "
        "program's own model",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/solar-open2-ep16-l8.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/test_solar2_serving.py holds "
        "this configuration's count to the program's own model",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/keye-vl2-30b-l6.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/perfbench/"
        "test_perfbench_keyevl2.py holds this configuration's count to the "
        "program's own model and tower",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/ling3-flash-ep8-l7.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/test_ling3_serving.py holds "
        "this configuration's count to the program's own model",
    "tests/perfbench/test_perfbench_flops.py::"
    "test_param_count_matches_the_programs_model"
    "[perfbench/configs/evabyte-6.5b-pp4-l8.json]":
        "perfbench/flops.py gpt_param_count counts the GPT-2 block only "
        "(a benchmark PR's to extend); tests/perfbench/"
        "test_perfbench_evabyte.py holds this configuration's count to the "
        "program's own model",
    "tests/perfbench/test_perfbench_traffic.py::"
    "test_order_seed_pins_tenants_and_lengths_and_leaves_the_seed_the_tokens"
    "[mixed-longshort]":
        "its last line knows ONE closed-loop file with `order_seed`, "
        "longdoc-gen (a benchmark PR's to extend); tests/perfbench/"
        "test_perfbench_trinity.py holds this file's pinned order and the "
        "seed's tokens",
    "tests/perfbench/test_perfbench_traffic.py::"
    "test_order_seed_pins_tenants_and_lengths_and_leaves_the_seed_the_tokens"
    "[bytedocs-longctx]":
        "its last line knows ONE closed-loop file with `order_seed`, "
        "longdoc-gen (a benchmark PR's to extend); tests/perfbench/"
        "test_perfbench_evabyte.py holds this file's pinned order and the "
        "seed's tokens",
}
# the same for every case of one test and cell: (node id's start, reason)
_CANNOT_APPLY_FROM = (
    ("tests/perfbench/test_perfbench_rehearsal.py::"
     "test_traced_rehearsal_reports_a_shared_entry_in_each_cell_it_lists"
     "[jamba2-3b-serve-chat-peak-",
     "test_perfbench_rehearsal.py's TINY_REPORTS has a row for the cells of "
     "PR 37 alone (a benchmark PR's to extend); tests/perfbench/"
     "test_perfbench_jamba2.py makes this cell's traced rehearsal and holds "
     "every entry that lists it"),
    ("tests/perfbench/test_perfbench_rehearsal.py::"
     "test_traced_rehearsal_reports_a_shared_entry_in_each_cell_it_lists"
     "[axk1-l6-serve-docqa-latent-",
     "test_perfbench_rehearsal.py's TINY_REPORTS has a row for the cells of "
     "PR 37 alone (a benchmark PR's to extend); tests/perfbench/"
     "test_perfbench_axk1.py makes this cell's traced rehearsal and holds "
     "every entry that lists it"),
    ("tests/perfbench/test_perfbench_rehearsal.py::"
     "test_traced_rehearsal_reports_a_shared_entry_in_each_cell_it_lists"
     "[dsv32-l5-serve-longqa-sparse-",
     "test_perfbench_rehearsal.py's TINY_REPORTS has a row for the cells of "
     "PR 37 alone (a benchmark PR's to extend); tests/perfbench/"
     "test_perfbench_dsv32.py makes this cell's traced rehearsal and holds "
     "every entry that lists it"),
    ("tests/perfbench/test_perfbench_rehearsal.py::"
     "test_traced_rehearsal_reports_a_shared_entry_in_each_cell_it_lists"
     "[trinity-l5-serve-mixed-longshort-",
     "test_perfbench_rehearsal.py's TINY_REPORTS has a row for the cells of "
     "PR 37 alone (a benchmark PR's to extend); tests/perfbench/"
     "test_perfbench_trinity.py makes this cell's traced rehearsal and holds "
     "every entry that lists it"),
    ("tests/perfbench/test_perfbench_rehearsal.py::"
     "test_traced_rehearsal_reports_a_shared_entry_in_each_cell_it_lists"
     "[longcat-l4-serve-rollout-skewed-",
     "test_perfbench_rehearsal.py's TINY_REPORTS has a row for the cells of "
     "PR 37 alone (a benchmark PR's to extend); tests/perfbench/"
     "test_perfbench_longcat.py makes this cell's traced rehearsal and holds "
     "every entry that lists it"),
    ("tests/perfbench/test_perfbench_rehearsal.py::"
     "test_traced_rehearsal_reports_a_shared_entry_in_each_cell_it_lists"
     "[solar2-l8-serve-docreason-mixed-",
     "test_perfbench_rehearsal.py's TINY_REPORTS has a row for the cells of "
     "PR 37 alone (a benchmark PR's to extend); tests/perfbench/"
     "test_perfbench_solar2.py makes this cell's traced rehearsal and holds "
     "the entries that list it"),
    ("tests/perfbench/test_perfbench_rehearsal.py::"
     "test_traced_rehearsal_reports_a_shared_entry_in_each_cell_it_lists"
     "[keyevl2-l6-serve-pagesqa-sparse-",
     "test_perfbench_rehearsal.py's TINY_REPORTS has a row for the cells of "
     "PR 37 alone (a benchmark PR's to extend); tests/perfbench/"
     "test_perfbench_keyevl2.py makes this cell's traced rehearsal and holds "
     "every entry that lists it"),
    ("tests/perfbench/test_perfbench_rehearsal.py::"
     "test_traced_rehearsal_reports_a_shared_entry_in_each_cell_it_lists"
     "[ling3-l7-serve-reason-widebatch-",
     "test_perfbench_rehearsal.py's TINY_REPORTS has a row for the cells of "
     "PR 37 alone (a benchmark PR's to extend); tests/perfbench/"
     "test_perfbench_ling3.py makes this cell's traced rehearsal and holds "
     "every entry that lists it"),
    ("tests/perfbench/test_perfbench_rehearsal.py::"
     "test_traced_rehearsal_reports_a_shared_entry_in_each_cell_it_lists"
     "[evabyte-l8-serve-bytedocs-longctx-",
     "test_perfbench_rehearsal.py's TINY_REPORTS has a row for the cells of "
     "PR 37 alone (a benchmark PR's to extend); tests/perfbench/"
     "test_perfbench_evabyte.py makes this cell's traced rehearsal and holds "
     "every entry that lists it"),
)


# A test that holds a cell to its PLACE in BENCHMARK.json's lists (last,
# alone) as they stood when it was written: it runs on the benchmark without
# the cells added since (a later cell's own test holds the lists as they
# are). (node id, the names of the cells and entries it did not know)
# The same for PER-LAYER ENTRIES added since that list every cell (PR 51:
# the start-up readers): such a test counts the entries that list its cell
# and pins the tail of ``per_layer``, so it is shown neither.
_SETUP_ENTRIES = ("setup_trace_lower_s", "setup_cache_load_s",
                  "setup_compile_s", "setup_programs")
# (PR 53: the LongCat-Flash cell and the two entries it brought)
_LONGCAT = ("longcat-l4-serve-rollout-skewed", "moe_zero_pairs_share",
            "moe_zero_busy_share")
# (PR 56: the Solar-Open2 cell and the five entries it brought)
_SOLAR2 = ("solar2-l8-serve-docreason-mixed", "kda_mix_busy_share",
           "kda_chunk_busy_share", "kda_step_busy_share",
           "kda_chunk_roofline", "kda_step_roofline")
# (PR 61: the Keye-VL-2.0 cell and the seven entries it brought)
_KEYEVL2 = ("keyevl2-l6-serve-pagesqa-sparse",
            "gqa_sparse_prefill_busy_share", "gqa_sparse_prefill_roofline",
            "tower_busy_share", "image_rows_share", "images_skipped_share",
            "gqa_sparse_decode_roofline", "gqa_selected_rows_share")
# (PR 64: the Ling-3.0-flash cell and the three entries it brought)
_LING3 = ("ling3-l7-serve-reason-widebatch", "mla_qk_norm_busy_share",
          "moe_group_tokens_share", "latent_bytes_share")
# (PR 66: the EvaByte cell and the five entries it brought)
_EVABYTE = ("evabyte-l8-serve-bytedocs-longctx", "eva_decode_roofline",
            "eva_pool_busy_share", "eva_summary_rows_share",
            "eva_rows_read_share", "eva_summary_bytes_share")
# (PR 68: the four entries of the step's own account, which list the
# thirteen closed-loop cells: no cell's test knew them)
_STEP_ACCOUNT = ("batch.lanes_prefilling_share", "batch.lanes_waiting_share",
                 "batch.step_prefill_time_share", "batch.step_caller_ms_p50")
_WRITTEN_BEFORE = {
    "tests/perfbench/test_perfbench_lfm2.py::"
    "test_every_width_is_the_published_one_and_only_the_depth_is_cut":
        ("jamba2-3b-serve-chat-peak", "axk1-l6-serve-docqa-latent",
         "dsv32-l5-serve-longqa-sparse", "trinity-l5-serve-mixed-longshort")
        + _LONGCAT + _SOLAR2 + _KEYEVL2 + _LING3 + _EVABYTE + _STEP_ACCOUNT,
    "tests/perfbench/test_perfbench_jamba2.py::"
    "test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved":
        ("axk1-l6-serve-docqa-latent", "dsv32-l5-serve-longqa-sparse",
         "trinity-l5-serve-mixed-longshort") + _SETUP_ENTRIES + _LONGCAT
        + _SOLAR2 + _KEYEVL2 + _LING3 + _EVABYTE + _STEP_ACCOUNT,
    "tests/perfbench/test_perfbench_axk1.py::"
    "test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved":
        ("dsv32-l5-serve-longqa-sparse", "trinity-l5-serve-mixed-longshort")
        + _SETUP_ENTRIES + _LONGCAT + _SOLAR2 + _KEYEVL2 + _LING3 + _EVABYTE
        + _STEP_ACCOUNT,
    "tests/perfbench/test_perfbench_dsv32.py::"
    "test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved":
        ("trinity-l5-serve-mixed-longshort",) + _SETUP_ENTRIES + _LONGCAT
        + _SOLAR2 + _KEYEVL2 + _LING3 + _EVABYTE + _STEP_ACCOUNT,
    "tests/perfbench/test_perfbench_trinity.py::"
    "test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved":
        _SETUP_ENTRIES + _LONGCAT + _SOLAR2 + _KEYEVL2 + _LING3 + _EVABYTE
        + _STEP_ACCOUNT,
    "tests/perfbench/test_perfbench_longcat.py::"
    "test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved":
        _SOLAR2 + _KEYEVL2 + _LING3 + _EVABYTE + _STEP_ACCOUNT,
    "tests/perfbench/test_perfbench_solar2.py::"
    "test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved":
        _KEYEVL2 + _LING3 + _EVABYTE + _STEP_ACCOUNT,
    "tests/perfbench/test_perfbench_keyevl2.py::"
    "test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved":
        _LING3 + _EVABYTE + _STEP_ACCOUNT,
    "tests/perfbench/test_perfbench_ling3.py::"
    "test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved":
        _EVABYTE + _STEP_ACCOUNT,
    "tests/perfbench/test_perfbench_evabyte.py::"
    "test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved":
        _STEP_ACCOUNT,
}


@pytest.fixture(autouse=True, scope="module")
def _no_other_files_events():
    """A test file starts with none of another file's events: the event log
    is the process's, a worker runs file after file, and a check that holds
    a run to NO fault event (``perfbench/serving.py`` ``serving_checks``,
    asked in-process by two files of ``tests/perfbench/``) would else read
    the faults that a file before it in the worker injected on purpose."""
    from fleetx_tpu.obs import get_event_log

    get_event_log().clear()


@pytest.fixture(autouse=True)
def _benchmark_as_the_test_knew_it(request, monkeypatch):
    later = _WRITTEN_BEFORE.get(request.node.nodeid)
    if not later:
        return
    import copy

    from perfbench import harness

    real = harness.load_json

    def load_json(*parts):
        data = real(*parts)
        if parts != ("BENCHMARK.json",):
            return data
        data = copy.deepcopy(data)
        data["workloads"] = [w for w in data["workloads"]
                             if w["name"] not in later]
        data["per_layer"] = [m for m in data["per_layer"]
                             if m["name"] not in later]
        for group in ("end_to_end", "per_layer"):
            for m in data[group]:
                if "workloads" in m:
                    m["workloads"] = [w for w in m["workloads"]
                                      if w not in later]
            data[group] = [m for m in data[group] if m.get("workloads", [1])]
        used = {w["config"] for w in data["workloads"]}
        data["configs"] = [c for c in data["configs"] if c["name"] in used]
        return data

    monkeypatch.setattr(harness, "load_json", load_json)
    if hasattr(request.module, "BENCH"):  # (a file that read it as it loaded)
        monkeypatch.setattr(request.module, "BENCH",
                            load_json("BENCHMARK.json"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """A subprocess that outlived its ``timeout=`` fails its test with what
    it had written (``TimeoutExpired`` holds it; its message does not)."""
    report = (yield).get_result()
    error = call.excinfo and call.excinfo.value
    if isinstance(error, subprocess.TimeoutExpired):
        for name in ("stdout", "stderr"):
            text = getattr(error, name) or ""
            if isinstance(text, bytes):
                text = text.decode(errors="replace")
            report.sections.append(
                (f"{name} of the subprocess cut at {error.timeout} s",
                 text[-4000:]))


def pytest_collection_modifyitems(items):
    for item in items:
        reason = _CANNOT_APPLY.get(item.nodeid) or next(
            (why for start, why in _CANNOT_APPLY_FROM
             if item.nodeid.startswith(start)), None)
        if reason:
            item.add_marker(pytest.mark.skip(reason=reason))


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip(f"needs 8 virtual devices, have {len(devs)} "
                    "(FLEETX_TEST_PLATFORM=real?)")
    return devs
