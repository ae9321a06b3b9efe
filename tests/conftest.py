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
}


def pytest_collection_modifyitems(items):
    for item in items:
        reason = _CANNOT_APPLY.get(item.nodeid)
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
