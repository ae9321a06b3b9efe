"""What the readers of a gated grouped-attention stack share: from the
device trace, self time under the scopes ``attn_gate`` (the output gate's
projection, sigmoid and product: ``fleetx_tpu/models/gpt/hybrid.py``) and
``post_norm`` (the norms after attention and after the MLP:
``fleetx_tpu/models/gpt/mixed_stack.py``) and of the chunk kernel
``fleetx_prefill_gqa`` (``ops/pallas/prefill_gqa.py``), read from the same
trace file and by the same wire-format reader as ``_parts.py``. Empty for a
program that has no such scope or kernel (a parent commit's, another
configuration's)."""

from __future__ import annotations

import functools
import glob
import os
import re

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import _mla, _parts

SCOPES = ("attn_gate", "post_norm")
_FOUND = {name: re.compile(r"/%s(/|$)" % name) for name in SCOPES}
KERNEL = "fleetx_prefill_gqa"


def seconds_of(devices: dict) -> dict:
    """Device self seconds, averaged over the devices: ``total``; under each
    scope of ``SCOPES`` (the innermost one named wins); of the kernel
    (``kernel``) with its ``kernel_calls`` on the first device."""
    out = {"total": 0.0, "kernel": 0.0, "kernel_calls": 0,
           **dict.fromkeys(SCOPES, 0.0)}
    for number, rows in enumerate(devices.values()):
        rows = _parts._named(rows)
        timed = trace_reduce.self_times(
            [[i, r[3], r[4]] for i, r in enumerate(rows)])
        for index, _, _, self_ns in timed:
            seconds = self_ns / 1e9 / len(devices)
            text, op = rows[index][0], rows[index][1]
            out["total"] += seconds
            if KERNEL in text:
                out["kernel"] += seconds
                out["kernel_calls"] += number == 0
                continue
            found = [(m.start(), name) for name, scope in _FOUND.items()
                     for m in scope.finditer(op)]
            if found:
                out[max(found)[1]] += seconds
    return out


@functools.lru_cache(maxsize=2)
def _of_file(path: str, mtime: float) -> dict:
    return seconds_of(_parts.load_xplane(path))


def seconds(run):
    """:func:`seconds_of` the run's trace; None without one."""
    if not run.trace:
        return None
    files = glob.glob(os.path.join(harness.WORK, "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    return _of_file(files[0], os.path.getmtime(files[0]))


def share(run, scope: str):
    """The scope's share of device self time; None where no instruction
    carries it."""
    read = seconds(run)
    if not read or not read["total"] or not read[scope]:
        return None
    return read[scope] / read["total"]


def chunk_spans(run, inside=None) -> list:
    """``(full key rows, window key rows, query rows)`` of every prefill
    program that began inside the stretch ``inside`` (default: the measured
    window) and says what its chunk kernel's steps covered: the spans
    ``serving.prefill_chunk`` and, for a prompt prefilled in one program,
    ``serving.admit``. A kind of layer the configuration lacks reads 0."""
    a, b = inside or run.window
    return [(s.attrs.get("attn_full_key_rows", 0),
             s.attrs.get("attn_window_key_rows", 0),
             s.attrs["attn_query_rows"])
            for name in ("serving.prefill_chunk", "serving.admit")
            for s in run.spans_named(name)
            if "attn_query_rows" in s.attrs and a <= s.start_s <= b]


span_field = _mla.span_field
