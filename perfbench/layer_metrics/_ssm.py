"""What the readers of a stack with selective-scan layers share: the
``serving.decode`` spans that carry the lanes whose lane-resident state a
tick advances (``state_lanes``), the ``serving.admit`` and
``serving.prefill_chunk`` spans that carry the rows a prefill's scans run
over (``scan_rows``: ``fleetx_tpu/serving/engine.py`` sets both for a model
that keeps such state), and, from the device trace, self time under the
scopes ``ssm_mix`` (the whole mixer: a sub-part of ``attn``, the operator's
place in a layer), ``ssm_scan`` and ``ssm_step``
(``fleetx_tpu/models/gpt/mixed_stack.py``) and of the kernels
``fleetx_ssm_scan*`` / ``fleetx_ssm_step*`` (``ops/pallas/ssm_scan.py``),
read from the same trace file and by the same wire-format reader as
``_parts.py``. Empty for a program that has no such span field, scope or
kernel (a parent commit's, another configuration's)."""

from __future__ import annotations

import functools
import glob
import os
import re

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import _parts

_SCOPES = {"mix": re.compile(r"/ssm_mix(/|$)"),
           "scan_step": re.compile(r"/ssm_(scan|step)(/|$)")}
_KERNELS = {"scan": "fleetx_ssm_scan", "step": "fleetx_ssm_step"}


def span_field(run, names, field: str, inside=None) -> list:
    """``field`` of every span of ``names`` that carries it and began
    inside the stretch ``inside`` (default: the measured window)."""
    a, b = inside or run.window
    return [s.attrs[field] for name in names for s in run.spans_named(name)
            if field in s.attrs and a <= s.start_s <= b]


def seconds_of(devices: dict) -> dict:
    """Device self seconds, averaged over the devices of
    ``_parts.load_xplane``'s lists: ``total``; under each scope of
    ``_SCOPES``; and of each kernel of ``_KERNELS`` with its ``*_calls`` on
    the first device."""
    out = {"total": 0.0, **{k: 0.0 for k in (*_SCOPES, *_KERNELS)},
           **{k + "_calls": 0 for k in _KERNELS}}
    for number, rows in enumerate(devices.values()):
        rows = _parts._named(rows)
        timed = trace_reduce.self_times(
            [[i, r[3], r[4]] for i, r in enumerate(rows)])
        for index, _, _, self_ns in timed:
            seconds = self_ns / 1e9 / len(devices)
            text, op = rows[index][0], rows[index][1]
            out["total"] += seconds
            for key, scope in _SCOPES.items():
                if scope.search(op):
                    out[key] += seconds
            for key, mark in _KERNELS.items():
                if mark in text:
                    out[key] += seconds
                    out[key + "_calls"] += number == 0
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


def share(run, key: str):
    """A scope's share of device self time; None where no instruction
    carries the scope."""
    read = seconds(run)
    if not read or not read["total"] or not read[key]:
        return None
    return read[key] / read["total"]


def roofline(run, kernel: str, cost_of_call, per_call: list):
    """In percent, the least time the chip could take for the traced calls
    of ``kernel`` (memory-bound: ``cost_of_call(mean of per_call, model)``
    bytes over peak bytes/s, times the programs traced: the kernel's calls
    over the layers, every layer calling it once a program) over the time
    they took. None where the trace has no such call or the spans no such
    field."""
    from perfbench import flops

    read = seconds(run)
    model = run.cell.config["model"]
    if (not read or run.peaks is None or not per_call
            or not read[kernel + "_calls"] or "layer_types" not in model):
        return None
    ops, bytes_ = cost_of_call(sum(per_call) / len(per_call), model)
    programs = read[kernel + "_calls"] / len(model["layer_types"])
    least = flops.roofline_seconds(ops, bytes_, run.peaks)[0] * programs
    return 100.0 * least / read[kernel]
