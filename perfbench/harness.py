"""What every driver shares: finding a cell's files by name, the run
record the metric readers take, the chip check, the compile clock and the
profiler window. Nothing here knows a cell, a configuration or a metric by
name."""

from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import os
import shutil
import sys
import time
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "perfbench")
# everything a run writes (token file, trainer output, profiler trace)
WORK = os.path.join(ROOT, ".perfbench")
# kernel families by the mark their instructions carry in a device trace
KERNEL_FAMILIES = {"flash": "fleetx_flash_", "decode": "fleetx_decode",
                   "ce": "fleetx_ce_"}


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def with_tiny(data: dict, tiny: bool) -> dict:
    """``data`` without its ``tiny`` group, which is merged over it (one
    level into nested groups) for a CPU rehearsal."""
    data = dict(data)
    over = data.pop("tiny", {})
    if tiny:
        for key, value in over.items():
            if isinstance(value, dict) and isinstance(data.get(key), dict):
                data[key] = {**data[key], **value}
            else:
                data[key] = value
    return data


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files its names point to."""

    name: str
    chips: int
    config: dict        # perfbench/configs/<config>.json (the file named in BENCHMARK.json)
    traffic: dict       # perfbench/traffic/<traffic>.json
    deploy: dict        # perfbench/cells/<name>.json: layout and sizes on the chips
    end_to_end: list    # metric entries of BENCHMARK.json that this cell reports
    per_layer: list
    tiny: bool = False


def load_cell(name: str, tiny: bool = False) -> Cell:
    bench = load_json("BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json "
                         f"(have {[w['name'] for w in bench['workloads']]})")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    mine = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return Cell(
        name=name, chips=int(entry["chips"]), tiny=tiny,
        config=with_tiny(load_json(config["file"]), tiny),
        traffic=with_tiny(load_json("perfbench", "traffic",
                                    entry["traffic"] + ".json"), tiny),
        deploy=with_tiny(load_json("perfbench", "cells", name + ".json"), tiny),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def by_name(kind: str, name: str):
    """The module ``perfbench/<kind>/<name>.py`` (a driver or a metric
    reader), found by its name alone. A metric's name may carry a tag and
    a dot in front (``chat.tick_ms_p50``): a per-layer metric names ONE
    end-to-end metric it moves and is reported only where that one is, so
    a reader that serves cells with different end-to-end metrics appears
    under one tagged name for each, and is found by the part after the
    dot. Cells that report the same end-to-end metric share the entry:
    its ``workloads`` lists them (``batch.tick_ms_p50`` names the four
    closed-loop cells)."""
    return importlib.import_module(f"perfbench.{kind}.{name.split('.')[-1]}")


@dataclasses.dataclass
class Run:
    """What one run measured, as the metric readers see it. Times are
    ``time.perf_counter`` seconds of this process."""

    cell: Cell
    device: dict                      # platform, kind, count
    setup_s: float
    window: tuple                     # (start, end) of the measured window
    attempted: int
    failed: int
    correct: bool
    checks: dict                      # what ``correct`` was decided from
    samples: dict                     # lists and totals the driver recorded
    spans: list                       # the program's spans inside the window
    counters: dict                    # the program's counters after the window
    traced: Optional[tuple] = None    # (start, end) the profiler was open
    trace: Optional[dict] = None      # trace_reduce.reduce_trace(...) of it
    peaks: Optional[dict] = None      # peaks.py row of the device

    def before_trace(self, t: float) -> bool:
        """Whether time ``t`` lies before the profiler began to disturb
        the run (always, in a run that was not traced)."""
        return self.traced is None or t < self.traced[0]

    def spans_named(self, name: str, untraced_only: bool = False) -> list:
        out = [s for s in self.spans if s.name == name]
        if untraced_only and self.traced:
            a, b = self.traced
            out = [s for s in out if s.end_s < a or s.start_s > b]
        return out


def own_the_chip(chips: int, tiny: bool) -> dict:
    """First jax call: say what we run on; anything but a TPU with at least
    ``chips`` chips ends the run without a result (a rehearsal runs
    anywhere and never reports a device metric)."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} (cell needs {chips})")
    if not tiny and (device["platform"] != "tpu" or len(devices) < chips):
        sys.exit(f"perfbench: the cell needs {chips} TPU chip(s), jax found "
                 f"{len(devices)} x {device['platform']!r}: no result")
    if len(devices) < chips:
        sys.exit(f"perfbench: rehearsal needs {chips} devices, have "
                 f"{len(devices)} (set --xla_force_host_platform_device_count)")
    return device


def device_peaks(device: dict, tiny: bool):
    """The peaks row of the device (None in a rehearsal, which has none)."""
    from perfbench import peaks

    return None if tiny else peaks.peaks_for(device["kind"])


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes held on the fullest of the chips used: the peak of the
    live buffers plus the peak of the region the runtime reserves for the
    programs' temporaries (on a TPU ``peak_bytes_in_use`` leaves those out:
    a 345M train step read 4.3 GB there beside 7 GB of temporaries). 0
    where the backend does not report it, as on the CPU."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return int(max(s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0) for s in stats))


class CompileClock:
    """Seconds jax spent in backend compiles, persistent-cache hits and
    misses, and the times of every compile request, from jax's own
    monitoring events (copied from ``chip_smoke.py``)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = self.misses = 0
        self.stamps = []  # perf_counter of every backend compile request
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds
            self.stamps.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def inside(self, start: float, end: float) -> int:
        return sum(1 for t in self.stamps if start <= t <= end)

    def report(self) -> dict:
        return {"compile_s": self.compile_s, "cache_hits": self.hits,
                "cache_misses": self.misses}


def mosaic_calls(hlo_text: str, kernel_name: str) -> int:
    """Mosaic custom calls of the named Pallas kernel in optimized HLO
    (copied from ``chip_smoke.py``)."""
    return sum(1 for line in hlo_text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and kernel_name in line)


class ProfilerWindow:
    """The profiler, open for ``length_s`` late in the measured window of a
    ``--trace 1`` run (from four fifths of the window less its own length).
    Starting it stalls the host for 2-3 s and writing it out for 1-2 s (my
    chip run, PR 22), which an open loop feels as a queue: so it sits late,
    and the client-side readers take what came before it
    (:meth:`Run.before_trace`). ``poll(now)`` is called by the driver
    between steps or ticks and opens or closes the trace when due; it
    returns True from a call that did either (and so held the thread)."""

    def __init__(self, enabled: bool, length_s: float):
        self.enabled, self.length_s = enabled, length_s
        self.dir = os.path.join(WORK, "trace")
        self.opened = self.closed = None
        self.start_at = None

    def arm(self, window_start: float, seconds: float) -> None:
        if self.enabled:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.start_at = window_start + max(
                0.0, 0.8 * seconds - self.length_s)

    def poll(self, now: float) -> bool:
        if self.start_at is None or self.closed is not None:
            return False
        import jax

        if self.opened is None and now >= self.start_at:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # host spans, not every call
            options.host_tracer_level = 2
            self.opening = time.perf_counter()
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.opened = time.perf_counter()
            return True
        if self.opened is not None and now >= self.opened + self.length_s:
            self.closed = time.perf_counter()
            jax.profiler.stop_trace()
            self.stopped = time.perf_counter()
            return True
        return False

    def close(self) -> None:
        """End a trace the window's end overtook."""
        if self.opened is not None and self.closed is None:
            self.poll(float("inf"))

    @property
    def traced(self) -> Optional[tuple]:
        """``(start of opening, closed and written)``: the stretch the
        tracer, its start-up or its write-out disturbed."""
        if self.opened is None:
            return None
        return (self.opening, self.stopped)

    def reduce(self) -> Optional[dict]:
        from perfbench import trace_reduce

        files = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            return None
        return trace_reduce.reduce_trace(
            trace_reduce.load_xplane(files[0]), KERNEL_FAMILIES)


def program_spans(start: float) -> list:
    """The program's completed spans that began at or after ``start``."""
    from fleetx_tpu.obs.tracing import get_recorder

    return [s for s in get_recorder().spans() if s.start_s >= start]


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation), None when empty."""
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


def log(message: str) -> None:
    print(f"perfbench: {message}", flush=True)
