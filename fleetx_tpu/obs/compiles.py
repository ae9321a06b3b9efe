"""jax's compiles, seen from inside the program: spans and counters.

jax reports every step of building a program through ``jax.monitoring``,
with the program's name (``fun_name``), on the thread that asked for it.
:func:`install` registers ONE set of listeners for the process, and each
event they take becomes a completed span in the ring of
:mod:`fleetx_tpu.obs.tracing`, written after the fact (``end_s`` = the
callback's ``perf_counter``, ``start_s`` = ``end_s`` less the event's
seconds, ``parent`` = the span open on that thread), and seconds on a
counter:

- ``/jax/core/compile/jaxpr_trace_duration``: span ``jit.trace``,
  stage ``trace``;
- ``/jax/core/compile/jaxpr_to_mlir_module_duration``: span ``jit.lower``,
  stage ``lower``;
- ``/jax/core/compile/backend_compile_duration``: span ``jit.compile``,
  stage ``backend``;
- ``/jax/compilation_cache/cache_retrieval_time_sec``: the ``load_s`` of
  the ``jit.compile`` that follows, stage ``cache_load``;
- ``/jax/compilation_cache/cache_hits`` and ``.../cache_misses``: the
  ``cache`` of the ``jit.compile`` that follows.

The backend-compile event wraps ``compiler.compile_or_get_cached``, so it
fires on a persistent-cache hit as on a miss, AFTER the hit or miss event
of the same program on the same thread: a ``jit.compile`` span's ``cache``
is ``"hit"`` (with ``load_s``, the seconds of the read), ``"miss"`` (the
program was compiled and written) or ``"off"`` (neither event came: the
cache is off, or jax does not keep the program: one with host callbacks).
Tracing and lowering are paid on a hit too: the cache's key is computed
from the lowered module. Tracing a function traces every jitted function
it calls (each ``jnp`` call is one: hundreds a train step), and jax times
each of them inside the outer one; a lowering traces too. ``jit.trace``
and ``jit.lower`` are the OUTERMOST such sections on their thread alone
(a trace inside a trace or a lowering is in the outer one's seconds, as
is the lowering of an eager call made while tracing), so these spans
never overlap each other and their seconds add up. What tells the depth
is the scalar that jax 0.9.0 records under the event's own name when a
timed section BEGINS (``dispatch.LogElapsedTimeContextManager.__enter__``):
a section that ends without having been announced raises here, so that a
jax which drops the scalar fails at its first trace and does not count a
step's trace several times over.

jax calls its listeners on these paths alone, never on a dispatch that
finds its executable, so a warm program pays nothing; and a span written
after the event needs no ``TraceAnnotation`` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import threading
import time

from jax import __version__ as jax_version
from jax import monitoring

from fleetx_tpu.obs.registry import get_registry
from fleetx_tpu.obs.tracing import Span, get_recorder

__all__ = ["install"]

_SPANS = {  # duration event -> (span name, the counter's stage)
    "/jax/core/compile/jaxpr_trace_duration": ("jit.trace", "trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("jit.lower", "lower"),
    "/jax/core/compile/backend_compile_duration": ("jit.compile", "backend"),
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
          "/jax/compilation_cache/cache_misses": "miss"}

_NESTING = frozenset(e for e, (name, _) in _SPANS.items()
                     if name != "jit.compile")
# of the program a thread is building: what the cache said of it, until
# its backend-compile event takes that, and how many timed sections of
# tracing and lowering are open (``open``)
_pending = threading.local()
_installed = False
_install_lock = threading.Lock()


def _seconds(stage: str, seconds: float) -> None:
    # fetched by name at each event (a compile is rare), so a registry that
    # a test cleared exposes the family again
    get_registry().counter(
        "fleetx_compile_seconds_total",
        "Seconds jax spent building programs, by stage",
        ("stage",)).labels(stage=stage).inc(seconds)


def _on_event(event: str, **_) -> None:
    cache = _CACHE.get(event)
    if cache is not None:
        _pending.cache = cache


def _on_begin(event: str, _value, **_) -> None:
    if event in _NESTING:
        _pending.open = getattr(_pending, "open", 0) + 1


def _on_duration(event: str, seconds: float, fun_name=None, **_) -> None:
    if event in _NESTING:
        open_ = getattr(_pending, "open", 0)
        if not open_:
            raise RuntimeError(
                f"jax {jax_version} timed {event} without announcing its "
                "start (jax 0.9.0 records a scalar under the event's name "
                "there): fleetx_tpu/obs/compiles.py cannot tell an outer "
                "trace from the traces inside it")
        _pending.open = open_ - 1
        if open_ > 1:
            return  # inside a trace or a lowering: that one holds its time
    if event == _CACHE_LOAD:
        _pending.load_s = seconds
        _seconds("cache_load", seconds)
        return
    named = _SPANS.get(event)
    if named is None:
        return
    end = time.perf_counter()
    name, stage = named
    attrs = {"fun_name": fun_name}
    if name == "jit.compile":
        said = vars(_pending)
        attrs["cache"] = said.pop("cache", "off")
        if "load_s" in said:
            attrs["load_s"] = said.pop("load_s")
        get_registry().counter(
            "fleetx_compile_programs_total",
            "Programs jax asked its backend for, by what the persistent "
            "cache said", ("cache",)).labels(cache=attrs["cache"]).inc()
    _seconds(stage, seconds)
    recorder = get_recorder()
    stack = recorder._stack()
    recorder.record(Span(
        name=name, start_s=end - seconds, end_s=end,
        thread_id=threading.get_ident(), depth=len(stack), attrs=attrs,
        parent=stack[-1] if stack else None))


def install() -> None:
    """Register the listeners with ``jax.monitoring``, once a process
    (``utils/compile_cache.enable_compile_cache`` calls this: every entry
    point's one call before its first jit)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        monitoring.register_event_listener(_on_event)
        monitoring.register_scalar_listener(_on_begin)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
