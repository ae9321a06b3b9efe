"""What the readers of a vision-language stack under a grouped indexer
share: from the device trace, self time of the kernel
``fleetx_gqa_sparse_prefill`` (``ops/pallas/prefill_gqa.py``; it runs inside
the scope ``dsa_attn`` and is counted with it by ``_dsa.py`` AND by itself
here); of a TICK'S attention under the selection (``tick_attn``: every
instruction under ``dsa_attn`` of a program that runs the paged decode
kernel ``fleetx_decode_paged``, which is the gather of the chosen rows out
of the pool AND the kernel over the compact pool they are gathered into:
the compiler keeps that pool in VMEM, so the kernel's own events hold no
read of HBM and the gather's hold all of them); and under the tower's scopes
``tower`` (its whole program), ``vit_embed``, ``vit_attn``, ``vit_mlp``,
``vit_project`` (``fleetx_tpu/models/vision/vit.py``,
``serving/rows_in.py``); and the
spans ``serving.admit`` of admissions with images (``images``,
``image_rows``, ``images_skipped``: what the counters of the same names sum
over the engine's life) and ``serving.tower``. Read from the same trace
file and by the same wire-format reader as ``_parts.py``. Empty for a
program that has no such scope, kernel or span field (a parent commit's,
another configuration's)."""

from __future__ import annotations

import functools
import glob
import os
import re

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import _parts

_TOWER = re.compile(r"/(tower|vit_embed|vit_attn|vit_mlp|vit_project)(/|$)")
_ATTN = re.compile(r"/dsa_attn(/|$)")
KERNEL = "fleetx_gqa_sparse_prefill"
TICK_KERNEL = "fleetx_decode_paged"


def seconds_of(devices: dict) -> dict:
    """Device self seconds, averaged over the devices: ``total``; under the
    tower's scopes (``tower``); of the chunk's kernel (``kernel``) with its
    ``kernel_calls`` on the first device; of a tick's attention under the
    selection (``tick_attn``: module docstring) with the ``tick_calls`` of
    its kernel on the first device."""
    out = {"total": 0.0, "tower": 0.0, "kernel": 0.0, "kernel_calls": 0,
           "tick_attn": 0.0, "tick_calls": 0}
    for number, rows in enumerate(devices.values()):
        rows = _parts._named(rows)
        ticks = {r[2] for r in rows if TICK_KERNEL in r[0]}
        timed = trace_reduce.self_times(
            [[i, r[3], r[4]] for i, r in enumerate(rows)])
        for index, _, _, self_ns in timed:
            seconds = self_ns / 1e9 / len(devices)
            text, op, program = rows[index][:3]
            out["total"] += seconds
            if KERNEL in text:
                out["kernel"] += seconds
                out["kernel_calls"] += number == 0
            elif TICK_KERNEL in text:
                out["tick_attn"] += seconds
                out["tick_calls"] += number == 0
            elif program in ticks and _ATTN.search(op):
                out["tick_attn"] += seconds
            elif _TOWER.search(op):
                out["tower"] += seconds
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
    """``key``'s share of device self time; None where no instruction
    carries it."""
    read = seconds(run)
    if not read or not read["total"] or not read[key]:
        return None
    return read[key] / read["total"]


def admissions(run) -> list:
    """``(prompt rows, image rows, images, images skipped)`` of every
    admission with images that began inside the measured window."""
    a, b = run.window
    return [(s.attrs["prompt_len"], s.attrs["image_rows"], s.attrs["images"],
             s.attrs["images_skipped"])
            for s in run.spans_named("serving.admit")
            if "image_rows" in s.attrs and a <= s.start_s <= b]
