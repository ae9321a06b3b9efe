"""Device time by model part: what the ``*_device_share`` readers share.

``reduce_trace`` names device time by instruction (``fusion``, ``copy``,
``convert``). This module names it by the PART of the model the
instruction belongs to, from the ``op_name`` the compiler keeps for it:
flax's module path (``.../layers/layer/attn/qkv_proj/dot_general``) plus
the ``jax.named_scope``s the program sets where flax has no name
(``cache_write``, ``cached_forward``, ``sampler``, ``lanes``, ``embed``,
``logits``, ``loss``, ``optimizer``, ``sentry``; docs/OBSERVABILITY.md). On a TPU v5e
(looked at by hand, PR 23) the ``.xplane.pb`` keeps the ``op_name`` not on
the event but on the event's METADATA, as the stat ``tf_op``, beside
``program_id``; ``jax.profiler.ProfileData`` does not show metadata stats,
so :func:`load_xplane` reads the protobuf's wire format itself (varints and
length-delimited fields: nothing but the standard library).

Some instructions the compiler makes itself carry no ``op_name``: the
f32-to-bf16 casts of whole weight stacks hoisted out of the layer scan,
and the copies of whole loop-carried buffers. They are named
``<scope>`` after the scope that marks their program (a program with
``cached_forward`` instructions serves, one with ``optimizer``
instructions trains), and the rules below place them.

ONE table, :data:`RULES`, first match wins. Kernel families
(``harness.KERNEL_FAMILIES``) and collectives are taken out first, as
``reduce_trace`` does, so parts + families + ``collectives`` partition
device self time: :func:`shares` sums to 1.

``python3 perfbench/layer_metrics/_parts.py dump <xplane.pb> <out.json>
[<max_ms> [<skip_ms>]]`` writes the plain lists with the ``op_name``s
kept, which is how ``perfbench/fixtures/*_parts_v5e.json`` were recorded.
"""

from __future__ import annotations

import collections
import functools
import glob
import json
import os
import re
import sys

if __name__ == "__main__":  # run as a script: make ``perfbench`` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from perfbench import harness, trace_reduce  # noqa: E402

PARTS = ("recast", "cache_move", "attn", "mlp", "head", "update", "carry",
         "unscoped")
# scopes that say what a whole program is, for its instructions without
# an op_name (module docstring)
PROGRAM_MARKS = ("cached_forward", "optimizer")

def _scope(*names: str) -> str:
    """Pattern of one path element of an op_name that is one of the scopes
    ``names``, bare or as transforms wrap it (``jvp(loss)``,
    ``transpose(jvp(loss))``)."""
    return r"/(?:[a-z_]+\()*(?:%s)\)*(?=/|$)" % "|".join(names)


# the layer scan's own instructions, outside every layer: slices of its
# stacked inputs, updates of its stacked outputs, copies and prefetches of
# its carry, its counter, the calls across its remat boundary
_SCAN = (r"_decoder_stack(/while(/(body|cond)(/closed_call)?)?)?"
         r"(/[a-z_\-]+)?$")
_LAYER = r"/layer(_[0-9]+)?/"
# (part, instruction pattern or None, op_name pattern or None)
RULES = (
    ("recast", r"^convert$", r"^<(cached_forward|optimizer)>$"),
    ("cache_move", r"^(copy|while)", r"^<cached_forward>$"),
    ("cache_move", None, _scope("cache_write")),
    ("cache_move", None, _scope("cached_forward") + ".*" + _SCAN),
    ("carry", r"^(copy|slice|while)", r"^<optimizer>$"),
    ("carry", None, _SCAN),
    ("update", None, _scope("optimizer", "sentry")),
    ("head", None, _scope("sampler", "lanes", "logits", "loss", "embed",
                          "embed_dropout", "final_norm")),
    ("attn", None, _LAYER + "(attn|norm1|attn_dropout)/"),
    ("mlp", None, _LAYER + "(mlp|moe_mlp|norm2|mlp_dropout)/"),
)
_RULES = tuple((part, instr and re.compile(instr), op and re.compile(op))
               for part, instr, op in RULES)
NAME_CHARS = 400  # of an instruction's text kept in a fixture


# ------------------------------------------------------- the .xplane.pb

def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: ints for varint
    and fixed fields, a memoryview for a length-delimited one."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"xplane: wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _entry(view):
    """``(key, value bytes)`` of one protobuf map entry."""
    key, value = 0, b""
    for number, field in _fields(view):
        if number == 1:
            key = field
        elif number == 2:
            value = field
    return key, value


def _plane(view) -> dict:
    """Name, lines ``{name: (timestamp_ns, [event views])}``, event
    metadata ``{id: (name, {stat name: value})}`` of one XPlane."""
    name, lines, metadata, stat_names = "", {}, {}, {}
    for number, field in _fields(view):
        if number == 2:
            name = _text(field)
        elif number == 3:
            line_name, stamp, events = "", 0, []
            for n, f in _fields(field):
                if n == 2:
                    line_name = _text(f)
                elif n == 3:
                    stamp = f
                elif n == 4:
                    events.append(f)
            lines[line_name] = (stamp, events)
        elif number == 4:
            metadata.update([_entry(field)])
        elif number == 5:
            key, value = _entry(field)
            stat_names[key] = next(
                (_text(f) for n, f in _fields(value) if n == 2), "")
    for key, value in metadata.items():
        meta_name, stats = "", {}
        for n, f in _fields(value):
            if n == 2:
                meta_name = _text(f)
            elif n == 5:
                stat = dict(_fields(f))  # XStat: 1 metadata id, 2-7 value
                raw = (stat_names.get(stat[7]) if 7 in stat else next(
                    (stat[k] for k in (5, 3, 4, 2) if k in stat), None))
                stats[stat_names.get(stat.get(1), "")] = (
                    _text(raw) if isinstance(raw, memoryview) else raw)
        metadata[key] = (meta_name, stats)
    return {"name": name, "lines": lines, "metadata": metadata}


def _events(plane: dict, line: str):
    """``(metadata id, start_ns, dur_ns)`` of a line's events."""
    stamp, events = plane["lines"].get(line, (0, ()))
    for view in events:
        event = dict(_fields(view))
        yield event.get(1, 0), stamp + event.get(2, 0) / 1e3, event.get(3, 0) / 1e3


def load_xplane(path: str, name_chars: int = trace_reduce.NAME_CHARS) -> dict:
    """``{plane: [[instruction text, op_name, program, start_ns, dur_ns],
    ...]}`` for every device plane of an ``.xplane.pb``, sorted by start
    with enclosing events first. ``program`` is the executed program's name
    (``jit_prefill``), found through the instruction's ``program_id``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, view in _fields(space):
        if number != 1:
            continue
        # a plane's name is its second field: skip the others unparsed
        name = next((_text(f) for n, f in _fields(view) if n == 2), "")
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        plane = _plane(view)
        programs = {}
        for meta, _, _ in _events(plane, trace_reduce.MODULES_LINE):
            module = plane["metadata"][meta][0]      # jit_prefill(1234)
            programs[module.rsplit("(", 1)[-1].rstrip(")")] = \
                trace_reduce.module_name(module)
        rows = []
        for meta, start, dur in _events(plane, trace_reduce.OPS_LINE):
            text, stats = plane["metadata"][meta]
            rows.append([text[:name_chars],
                         str(stats.get("tf_op", "")).rstrip(":"),
                         programs.get(str(stats.get("program_id")), ""),
                         start, dur])
        out[name] = sorted(rows, key=lambda r: (r[3], -r[4]))
    return out


# ------------------------------------------------------------ the parts

def part_of(instruction: str, op_name: str) -> str:
    """The part of :data:`PARTS` an instruction (``fusion``, ``copy``:
    ``trace_reduce.instruction``) with this ``op_name`` belongs to."""
    for part, instr, op in _RULES:
        if ((instr is None or instr.search(instruction))
                and (op is None or op.search(op_name))):
            return part
    return "unscoped"


def _named(rows):
    """``rows`` with every empty op_name replaced by ``<mark>``, the scope
    that marks the row's program (``<>`` where none does)."""
    marks = {}
    for _, op, program, _, _ in rows:
        if op and program not in marks:
            mark = next((m for m in PROGRAM_MARKS
                         if re.search(_scope(m), op)), None)
            if mark:
                marks[program] = mark
    return [[text, op or f"<{marks.get(program, '')}>", program, start, dur]
            for text, op, program, start, dur in rows]


def self_seconds(devices: dict, families: dict = None) -> dict:
    """Device self time in seconds, averaged over the devices, by part of
    :data:`PARTS`, by kernel family and for ``collectives``; and ``top``,
    per part the instructions and op_names that hold most of it."""
    families = harness.KERNEL_FAMILIES if families is None else families
    seconds = collections.Counter({k: 0.0 for k in (*PARTS, *families,
                                                    "collectives")})
    top = collections.defaultdict(collections.Counter)
    for rows in devices.values():
        rows = _named(rows)
        timed = trace_reduce.self_times([[i, r[3], r[4]]
                                         for i, r in enumerate(rows)])
        for index, _, _, self_ns in timed:
            text, op = rows[index][0], rows[index][1]
            head = text.split(" = ", 1)[0]
            key = next((f for f, mark in families.items() if mark in head),
                       None)
            if key is None and trace_reduce.is_collective(text):
                key = "collectives"
            if key is None:
                instruction = trace_reduce.instruction(text)
                key = part_of(instruction, op)
                top[key][f"{instruction} {re.sub(r'[0-9]+', 'N', op)}"] += \
                    self_ns / 1e9 / len(devices)
            seconds[key] += self_ns / 1e9 / len(devices)
    return {"seconds": dict(seconds),
            "top": {k: v.most_common(8) for k, v in top.items()}}


def shares(devices: dict, families: dict = None) -> dict:
    """Share of device self time by part, kernel family and collectives:
    sums to 1. Empty when there is no device time."""
    seconds = self_seconds(devices, families)["seconds"]
    total = sum(seconds.values())
    return {k: v / total for k, v in seconds.items()} if total else {}


@functools.lru_cache(maxsize=2)
def _shares_of_file(path: str, mtime: float) -> dict:
    return shares(load_xplane(path))


def traced_shares(run) -> dict:
    """:func:`shares` of the trace this run wrote under ``harness.WORK``
    (it stays on disk after ``ProfilerWindow.reduce()``); empty for a run
    that was not traced or wrote none."""
    if not run.trace:
        return {}
    files = glob.glob(os.path.join(harness.WORK, "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return _shares_of_file(files[0], os.path.getmtime(files[0])) if files else {}


def read_share(run, part: str):
    """What a ``<part>_device_share`` reader returns: the part's share of
    device self time, None without a trace."""
    return traced_shares(run).get(part)


# --------------------------------------------------------- the fixtures

def _dump(path: str, out: str, max_ms=None, skip_ms=0.0) -> None:
    """Write the plain lists of ``path`` cut to ``max_ms`` of device
    activity from ``skip_ms`` after its start, in the format of
    ``trace_reduce``'s ``dump`` (its ``load_dump`` reads the file too)
    with two more tables beside ``names``: each name's ``op_names`` and
    ``programs`` entry."""
    devices = load_xplane(path, name_chars=NAME_CHARS)
    rest = trace_reduce.load_xplane(path, name_chars=NAME_CHARS)
    start = min(r[3] for rows in devices.values() for r in rows) + skip_ms * 1e6
    end = start + max_ms * 1e6 if max_ms else float("inf")
    table: dict = {}

    def pack(events):
        return [[table.setdefault(tuple(key), len(table)), round(s - start),
                 round(d)] for *key, s, d in events
                if start <= s and s + d <= end]

    # a host span that the cut crosses is kept, clipped to the cut
    host = [[n, "", "", max(s, start), min(s + d, end) - max(s, start)]
            for n, s, d in rest["host"] if s < end and s + d > start]
    packed = {"devices": {p: pack(rows) for p, rows in devices.items()},
              "modules": {p: pack([n, "", "", s, d] for n, s, d in rows)
                          for p, rows in rest["modules"].items()},
              "host": pack(host)}
    for i, kind in enumerate(("names", "op_names", "programs")):
        packed[kind] = [key[i] for key in table]
    with open(out, "w") as f:
        json.dump(packed, f, separators=(",", ":"))


def load_dump(path: str) -> dict:
    """The device lists (as :func:`load_xplane` gives them) back from a
    file written by ``dump``."""
    with open(path) as f:
        packed = json.load(f)
    names, ops, programs = (packed[k] for k in ("names", "op_names", "programs"))
    return {plane: [[names[i], ops[i], programs[i], float(s), float(d)]
                    for i, s, d in rows]
            for plane, rows in packed["devices"].items()}


if __name__ == "__main__":
    if len(sys.argv) < 4 or sys.argv[1] != "dump":
        sys.exit(__doc__)
    _dump(sys.argv[2], sys.argv[3], *map(float, sys.argv[4:6]))
