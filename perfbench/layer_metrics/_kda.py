"""What the readers of a stack with KDA (delta-rule) layers share: the
``serving.decode`` spans' ``state_lanes`` and the ``serving.admit`` /
``serving.prefill_chunk`` spans' ``scan_rows`` (``_ssm.span_field``: the
engine sets both for any lane-resident kind), and, from the device trace,
self time under the scopes ``kda_mix`` (the whole operator: a sub-part of
``attn``), ``kda_chunk`` and ``kda_step``
(``fleetx_tpu/models/gpt/mixed_stack.py``) and of the kernels
``fleetx_kda_chunk*`` / ``fleetx_kda_step*`` (``ops/pallas/kda.py``), read
from the same trace file and by the same wire-format reader as ``_parts.py``.
Empty for a program that has no such span field, scope or kernel (a parent
commit's, another configuration's)."""

from __future__ import annotations

import functools
import glob
import os
import re

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import _parts
from perfbench.layer_metrics._ssm import span_field  # noqa: F401

_SCOPES = {name: re.compile(rf"/kda_{name}(/|$)")
           for name in ("mix", "chunk", "step")}
_KERNELS = {"chunk_kernel": "fleetx_kda_chunk",
            "step_kernel": "fleetx_kda_step"}


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
    of ``kernel`` (the greater of ``cost_of_call(mean of per_call, model)``'s
    operations at the chip's peak and its bytes at peak bytes/s, times the
    programs traced: the kernel's calls over the layers, every layer calling
    it once a program) over the time they took. None where the trace has no
    such call or the spans no such field."""
    from perfbench import flops

    read = seconds(run)
    model = run.cell.config["model"]
    if (not read or run.peaks is None or not per_call
            or not read[kernel + "_calls"] or "kda_num_heads" not in model):
        return None
    ops, bytes_ = cost_of_call(sum(per_call) / len(per_call), model)
    programs = read[kernel + "_calls"] / len(model["layer_types"])
    least = flops.roofline_seconds(ops, bytes_, run.peaks)[0] * programs
    return 100.0 * least / read[kernel]
