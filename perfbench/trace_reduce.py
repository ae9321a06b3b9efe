"""From a profiler trace to numbers: the benchmark's own reduction.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU v5e (looked at by hand, PR 22) the planes are: one
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
executed program), ``XLA Ops`` (one event per executed HLO instruction,
named by the instruction's full text, container ops such as ``%while``
enclosing their bodies' events), ``Async XLA Ops`` and ``TC Overlay``; and
``/host:CPU`` with one line per thread, where ``TraceAnnotation`` spans
(the program's ``serving.tick``, ``train.step`` ...) sit on the thread
that opened them. A Pallas kernel's event begins ``%<kernel name>.<n> =``
and holds ``custom_call_target="tpu_custom_call"``. Host and device
events share one clock, to within about half a millisecond.

:func:`load_xplane` turns the file into plain lists; :func:`reduce_trace`
turns those into the busy union, the idle share, self time by instruction
and by kernel family, exposed collective time, and idle gaps attributed to
the host span open at that time. ``python3 perfbench/trace_reduce.py dump
<xplane.pb> <out.json> [<max_ms>]`` writes the plain lists, which is how
``perfbench/fixtures/`` was recorded.
"""

from __future__ import annotations

import collections
import json
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# dotted lower-case names: the program's span taxonomy, <subsystem>.<phase>
HOST_SPAN = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
# instruction names the compiler gives collectives (sync, -start and -done)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
NAME_CHARS = 1500


def load_xplane(path: str, name_chars: int = NAME_CHARS) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]}, "modules":
    {plane: [...]}, "host": [...]}`` from an ``.xplane.pb``: device
    instruction events (names cut to ``name_chars``), executed programs,
    and host span annotations, each list sorted by start."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = devices if line.name == OPS_LINE else modules
                    into[plane.name] = sorted(
                        ([e.name[:name_chars], float(e.start_ns),
                          float(e.duration_ns)] for e in line.events),
                        key=lambda ev: (ev[1], -ev[2]))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events if HOST_SPAN.match(e.name))
    host.sort(key=lambda ev: (ev[1], -ev[2]))
    return {"devices": devices, "modules": modules, "host": host}


def module_name(name: str) -> str:
    """``jit_prefill(1234567)`` -> ``jit_prefill``."""
    return name.split("(", 1)[0]


def instruction(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion``: the instruction's name without
    its number, which is what stays stable from run to run."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def is_collective(name: str) -> bool:
    return instruction(name).startswith(COLLECTIVES)


def self_times(events):
    """``[(name, start_ns, dur_ns, self_ns)]``: each event's duration less
    the part its enclosed events cover (events sorted by start, enclosing
    first)."""
    out, stack = [], []  # stack of [index, end_ns]
    for name, start, dur in events:
        while stack and start >= stack[-1][1] - 1e-6:
            stack.pop()
        if stack:
            out[stack[-1][0]][3] -= dur
        out.append([name, start, dur, dur])
        stack.append([len(out) - 1, start + dur])
    return out


def _union(intervals):
    """Merged ``[start, end]`` list of possibly overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _open_span(host, t):
    """Name of the innermost host span open at time ``t``, else None."""
    best = None
    for name, start, dur in host:
        if start > t:
            break
        if t < start + dur and (best is None or start >= best[1]):
            best = (name, start)
    return best and best[0]


def reduce_trace(trace: dict, families: dict, top: int = 10) -> dict:
    """Numbers of one traced window.

    ``families`` maps a family name to the substring that marks its kernels
    in an instruction's name (``{"flash": "fleetx_flash_"}``). Returns, with
    seconds averaged over the devices unless said otherwise: ``window_s``
    (first device event to last), ``busy_s`` (union of instruction
    intervals), ``idle_share`` (1 - busy/window on the WORST device),
    ``family_s`` / ``family_calls`` (self time and events per family),
    ``collective_exposed_s`` (self time of collective instructions, during
    which the core runs nothing else; worst device), ``xla_s`` (self time
    outside the families and collectives), ``device_ops`` (top self time by
    instruction name) and ``idle_gaps`` (idle seconds by the host span open
    in the middle of each gap, worst device). ``kernel_events`` lists the
    family events ``(family, name, self_s)`` of the first device for the
    roofline readers, and ``module_s`` the device seconds of every executed
    program of the first device, by program name."""
    per_device = []
    for plane in sorted(trace["devices"]):
        events = trace["devices"][plane]
        if not events:
            continue
        timed = self_times(events)
        t0 = min(ev[1] for ev in events)
        t1 = max(ev[1] + ev[2] for ev in events)
        busy = _union([(ev[1], ev[1] + ev[2]) for ev in events])
        by_name = collections.Counter()
        family_s = collections.Counter()
        family_calls = collections.Counter()
        kernel_events = []
        exposed = xla = 0.0
        for name, _, _, self_ns in timed:
            by_name[instruction(name)] += self_ns
            family = next((f for f, mark in families.items()
                           if mark in name.split(" = ", 1)[0]), None)
            if family is not None:
                family_s[family] += self_ns
                family_calls[family] += 1
                kernel_events.append((family, name, self_ns / 1e9))
            elif is_collective(name):
                exposed += self_ns
            else:
                xla += self_ns
        gaps = collections.Counter()
        edges = [t0] + [t for iv in busy for t in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_open_span(trace["host"], (a + b) / 2) or "no span"] += b - a
        per_device.append({
            "plane": plane, "window_s": (t1 - t0) / 1e9,
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "by_name": by_name, "family_s": family_s,
            "family_calls": family_calls, "exposed_s": exposed / 1e9,
            "xla_s": xla / 1e9, "gaps": gaps,
            "kernel_events": kernel_events})
    if not per_device:
        return {}
    n = len(per_device)
    mean = lambda key: sum(d[key] for d in per_device) / n  # noqa: E731
    worst = max(per_device, key=lambda d: 1 - d["busy_s"] / d["window_s"])
    module_s = collections.defaultdict(list)
    first = sorted(trace["devices"])[0]
    for name, _, dur in trace.get("modules", {}).get(first, []):
        module_s[module_name(name)].append(dur / 1e9)
    names = collections.Counter()
    for d in per_device:
        for k, v in d["by_name"].items():
            names[k] += v / n / 1e9
    return {
        "devices": n,
        "window_s": mean("window_s"),
        "busy_s": mean("busy_s"),
        "idle_share": 1 - worst["busy_s"] / worst["window_s"],
        "family_s": {f: sum(d["family_s"][f] for d in per_device) / n / 1e9
                     for f in families},
        "family_calls": {f: per_device[0]["family_calls"][f] for f in families},
        "collective_exposed_s": max(d["exposed_s"] for d in per_device),
        "xla_s": mean("xla_s"),
        "device_ops": [[k, v] for k, v in names.most_common(top)],
        "idle_gaps": [[k, v / 1e9] for k, v in worst["gaps"].most_common(top)],
        "kernel_events": per_device[0]["kernel_events"],
        "module_s": dict(module_s),
    }


def _dump(path: str, out: str, max_ms: float | None) -> None:
    """Write the plain lists of ``path``, cut to the first ``max_ms`` of
    device activity, with the names in a table (small fixtures)."""
    trace = load_xplane(path, name_chars=NAME_CHARS)
    start = min(ev[1] for evs in trace["devices"].values() for ev in evs)
    end = start + max_ms * 1e6 if max_ms else float("inf")
    table: dict = {}

    def pack(events):
        return [[table.setdefault(n, len(table)), round(s - start), round(d)]
                for n, s, d in events if start <= s and s + d <= end]

    # a host span that the cut crosses is kept, clipped to the cut
    host = [[n, max(s, start), min(s + d, end) - max(s, start)]
            for n, s, d in trace["host"] if s < end and s + d > start]
    packed = {"devices": {p: pack(e) for p, e in trace["devices"].items()},
              "modules": {p: pack(e) for p, e in trace["modules"].items()},
              "host": pack(host)}
    packed["names"] = list(table)
    with open(out, "w") as f:
        json.dump(packed, f, separators=(",", ":"))


def load_dump(path: str) -> dict:
    """The plain lists back from a file written by ``dump``."""
    with open(path) as f:
        packed = json.load(f)
    names = packed["names"]
    unpack = lambda evs: [[names[i], float(s), float(d)] for i, s, d in evs]  # noqa: E731
    return {"devices": {p: unpack(e) for p, e in packed["devices"].items()},
            "modules": {p: unpack(e) for p, e in packed["modules"].items()},
            "host": unpack(packed["host"])}


if __name__ == "__main__":
    if len(sys.argv) < 4 or sys.argv[1] != "dump":
        sys.exit(__doc__)
    _dump(sys.argv[2], sys.argv[3],
          float(sys.argv[4]) if len(sys.argv) > 4 else None)
