"""jax's compiles as the program's own spans and counters
(``fleetx_tpu/obs/compiles.py``, docs/OBSERVABILITY.md "Start-up"): every
trace, lowering and backend compile of a program is a completed span with
the program's name, under the span that was open when it happened, and
seconds on a counter; a warm call leaves nothing."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring

from fleetx_tpu.obs import compiles, get_recorder, get_registry, span
from fleetx_tpu.utils.compile_cache import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counters():
    snap = get_registry().snapshot()
    out = {}
    for family, label in (("fleetx_compile_programs_total", "cache"),
                          ("fleetx_compile_seconds_total", "stage")):
        for series in snap.get(family, {}).get("series", ()):
            out[family, series["labels"][label]] = series["value"]
    return out


def _moved(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def test_enabling_twice_installs_one_listener():
    enable_compile_cache()
    enable_compile_cache()
    assert monitoring.get_event_listeners().count(compiles._on_event) == 1
    assert monitoring.get_event_duration_listeners().count(
        compiles._on_duration) == 1
    assert monitoring.get_scalar_listeners().count(compiles._on_begin) == 1


def test_a_fresh_jit_call_leaves_three_spans_under_the_open_one():
    enable_compile_cache()

    @jax.jit
    def inner(x):
        return x * 2

    @jax.jit
    def obs_compiles_probe(x):
        return inner(x) + 1

    x = jnp.ones(3)  # (its own little programs compile out here)
    rec = get_recorder()
    rec.clear()
    before = _counters()
    with span("x.y"):
        obs_compiles_probe(x)
    after = _counters()
    *built, outer = rec.spans()
    assert outer.name == "x.y"
    # ONE trace (inner's lies inside the probe's), one lowering, one compile
    assert [(s.name, s.attrs["fun_name"]) for s in built] == [
        ("jit.trace", "obs_compiles_probe"),
        ("jit.lower", "jit(obs_compiles_probe)"),
        ("jit.compile", "jit(obs_compiles_probe)")]
    for s in built:
        assert s.parent == "x.y" and s.thread_id == outer.thread_id
        assert outer.start_s <= s.start_s <= s.end_s <= outer.end_s
    trace, lower, compile_ = built
    assert trace.end_s <= lower.end_s <= compile_.end_s
    assert compile_.attrs == {"fun_name": "jit(obs_compiles_probe)",
                              "cache": "off"}  # no cache on a CPU run
    # both counters moved by what the spans say
    assert _moved(before, after) == pytest.approx({
        ("fleetx_compile_programs_total", "off"): 1,
        ("fleetx_compile_seconds_total", "trace"): trace.duration_s,
        ("fleetx_compile_seconds_total", "lower"): lower.duration_s,
        ("fleetx_compile_seconds_total", "backend"): compile_.duration_s})
    # a second call finds its executable: no callback, no span
    rec.clear()
    obs_compiles_probe(x)
    assert rec.spans() == [] and _counters() == after


_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _section(event, seconds, fun_name, inner=()):
    """jax's own order of calls around one timed section
    (``dispatch.LogElapsedTimeContextManager``)."""
    monitoring.record_scalar(event, 0.0, fun_name=fun_name)
    for args in inner:
        _section(*args)
    monitoring.record_event_duration_secs(event, seconds, fun_name=fun_name)


@pytest.mark.parametrize("outer,inner,left", [
    (_TRACE, _TRACE, "jit.trace"),   # a jitted function called while tracing
    (_LOWER, _TRACE, "jit.lower"),   # a lowering rule that traces
    (_TRACE, _LOWER, "jit.trace"),   # an eager call made while tracing
], ids=["trace_in_trace", "trace_in_lower", "lower_in_trace"])
def test_the_outermost_section_alone_is_a_span(outer, inner, left):
    enable_compile_cache()
    rec = get_recorder()
    rec.clear()
    before = _counters()
    _section(outer, 2.0, "whole", inner=[(inner, 0.5, "part"),
                                         (inner, 0.25, "part")])
    _section(inner, 0.125, "after")  # on its own again: a span again
    assert [(s.name, s.attrs["fun_name"], s.duration_s)
            for s in rec.spans()] == [
        (left, "whole", pytest.approx(2.0)),
        (_spans_name(inner), "after", pytest.approx(0.125))]
    moved = _moved(before, _counters())
    assert sum(moved.values()) == pytest.approx(2.125)  # no second counted


def _spans_name(event):
    return compiles._SPANS[event][0]


def test_a_section_that_was_never_announced_raises():
    """What tells the depth is the scalar jax 0.9.0 records when a timed
    section begins: a jax that stops recording it must fail here and not
    count every inner trace as a program's own."""
    enable_compile_cache()
    with pytest.raises(RuntimeError, match="without announcing its start"):
        monitoring.record_event_duration_secs(_TRACE, 0.5, fun_name="f")


def test_a_compile_on_another_thread_has_no_parent_here():
    import threading

    enable_compile_cache()
    rec = get_recorder()
    x = jnp.ones(5)
    rec.clear()

    def work():
        jax.jit(lambda v: v - 3)(x)

    with span("x.y"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    (compile_,) = [s for s in rec.spans() if s.name == "jit.compile"]
    assert compile_.parent is None
    assert compile_.thread_id != threading.get_ident()


_CACHED = textwrap.dedent("""
    import json, jax, jax.numpy as jnp
    from fleetx_tpu.obs import get_recorder, get_registry
    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    assert enable_compile_cache()

    @jax.jit
    def obs_compiles_cached(x):
        return jnp.tanh(x) @ x.T

    x = jnp.ones((4, 4))
    for _ in range(2):
        obs_compiles_cached(x)
        jax.clear_caches()  # the executable goes; the directory keeps it
    snap = get_registry().snapshot()
    print(json.dumps({
        "spans": [[s.name, s.attrs, s.duration_s]
                  for s in get_recorder().spans()
                  if "obs_compiles_cached" in s.attrs["fun_name"]],
        "programs": {s["labels"]["cache"]: s["value"] for s in
                     snap["fleetx_compile_programs_total"]["series"]},
        "seconds": {s["labels"]["stage"]: s["value"] for s in
                    snap["fleetx_compile_seconds_total"]["series"]}}))
""")


def test_the_same_program_comes_back_from_the_cache_as_a_hit(tmp_path):
    """The persistent cache is the environment's (jax reads
    ``JAX_COMPILATION_CACHE_DIR`` as it is imported), so in a process of
    its own: first call a miss, after ``jax.clear_caches()`` a hit with
    the seconds of its read; tracing and lowering are paid both times."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", _CACHED], env=env,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    said = json.loads(proc.stdout.strip().splitlines()[-1])
    names = [name for name, _, _ in said["spans"]]
    assert names == ["jit.trace", "jit.lower", "jit.compile"] * 2
    (_, miss, _), (_, hit, hit_s) = [s for s in said["spans"]
                                     if s[0] == "jit.compile"]
    assert miss == {"fun_name": "jit(obs_compiles_cached)", "cache": "miss"}
    assert hit["cache"] == "hit" and 0 < hit["load_s"] <= hit_s
    # every backend compile of the process is counted once, by what the
    # cache said of it; the read's seconds are a stage of their own
    assert said["programs"]["hit"] >= 1 and said["programs"]["miss"] >= 1
    assert "off" not in said["programs"]
    assert said["seconds"]["cache_load"] >= hit["load_s"]
    assert said["seconds"]["backend"] >= hit_s
