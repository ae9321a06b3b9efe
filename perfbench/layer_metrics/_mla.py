"""What the readers of a latent-attention stack share: the ``serving.
decode`` spans that carry the live rows ONE layer's kernel call attends
over (``latent_rows``: ``fleetx_tpu/serving/engine.py`` sets it for a model
whose pool holds latents), and, from the device trace, self time under the
scopes ``mla_proj``, ``mla_absorb``, ``mla_kv_up``, ``mla_attn_prefill``
(``fleetx_tpu/models/gpt/latent.py``) and ``moe_shared``
(``fleetx_tpu/parallel/moe_share.py``) and of the kernel
``fleetx_mla_decode_paged`` (``ops/pallas/mla_decode.py``), read from the
same trace file and by the same wire-format reader as ``_parts.py``. Empty
for a program that has no such span field, scope or kernel (a parent
commit's, another configuration's)."""

from __future__ import annotations

import functools
import glob
import os
import re

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import _parts

_SCOPES = {name: re.compile(r"/%s(/|$)" % name) for name in (
    "mla_proj", "mla_absorb", "mla_kv_up", "mla_attn_prefill", "moe_shared")}
KERNEL = "fleetx_mla_decode_paged"


def span_field(run, names, field: str, inside=None) -> list:
    """``field`` of every span of ``names`` that carries it and began
    inside the stretch ``inside`` (default: the measured window)."""
    a, b = inside or run.window
    return [s.attrs[field] for name in names for s in run.spans_named(name)
            if field in s.attrs and a <= s.start_s <= b]


def seconds_of(devices: dict) -> dict:
    """Device self seconds, averaged over the devices of
    ``_parts.load_xplane``'s lists: ``total``; under each scope of
    ``_SCOPES`` (the innermost one named wins); of the kernel (``kernel``)
    with its ``kernel_calls`` on the first device."""
    out = {"total": 0.0, "kernel": 0.0, "kernel_calls": 0,
           **dict.fromkeys(_SCOPES, 0.0)}
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
            found = [(m.start(), name) for name, scope in _SCOPES.items()
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


def share(run, *keys: str):
    """The keys' share of device self time together; None where no
    instruction carries any of them."""
    read = seconds(run)
    took = sum(read[k] for k in keys) if read else 0.0
    if not read or not read["total"] or not took:
        return None
    return took / read["total"]
