"""Chip bring-up contracts that hold on the CPU (ISSUE 21): no device is
guessed, the compile cache can be placed from outside, and the serving
launcher keeps one process per chip."""

import os
import subprocess
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_peak_flops_raises_on_unlisted_device():
    from fleetx_tpu.utils.hw import UnknownDeviceKind, peak_flops_per_chip

    assert peak_flops_per_chip(
        types.SimpleNamespace(device_kind="TPU v5 lite")) == 197e12
    for kind in ("cpu", "TPU v9 mega", "NVIDIA A100"):
        with pytest.raises(UnknownDeviceKind):
            peak_flops_per_chip(types.SimpleNamespace(device_kind=kind))
    with pytest.raises(UnknownDeviceKind):
        peak_flops_per_chip(jax.devices()[0])  # this CPU run prints no MFU


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the helper names that directory and
    sets none in code. Unset, on the CPU: no cache at all (the in-checkout
    default is for accelerators)."""
    from fleetx_tpu.utils import compile_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_compilation_cache_include_metadata_in_key")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == saved[keys[0]]
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == saved[keys[0]]
        # op_names are read from device traces: a cached program must not
        # come back under another build's names
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    # one fixed, gitignored path inside the checkout: no pid, no timestamp
    assert compile_cache.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_replica_children_each_see_one_chip():
    from tools.serve import _replica_env

    envs = [_replica_env(i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    for env in envs:
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_serve_launcher_imports_stay_off_the_backend():
    """A chip belongs to one process: importing the launcher and what its
    parent process runs (router, RPC client, API server) initialises no
    jax backend — the parent then pins itself to the host platform before
    the router's first key derivation (run_fleet), so the replica children
    can own the chips."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import tools.serve\n"
        "from fleetx_tpu.serving.api.replica_client import ReplicaClient\n"
        "from fleetx_tpu.serving.api.server import ApiServer\n"
        "from fleetx_tpu.serving.router import ServingRouter\n"
        "from fleetx_tpu.utils.xla_flags import _backend_already_initialized\n"
        "assert not _backend_already_initialized()\n" % REPO)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-1500:]
