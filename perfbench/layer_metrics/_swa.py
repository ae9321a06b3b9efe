"""What the ``swa_*`` readers share: the ``serving.decode`` spans that carry
the live rows of a tick by class of layer (``full_rows``, ``window_rows``:
``fleetx_tpu/serving/engine.py`` sets them over a pool of two classes of
page), and device self time under the attention's two scopes,
``attn_full`` and ``attn_window`` (``fleetx_tpu/models/gpt/hybrid.py``),
read from the same trace file and by the same wire-format reader as
``_parts.py``, whose rules book both to ``attn`` (the kernels inside them
to their family). Empty for a program that has no such span field or
scope (a parent commit's, another configuration's)."""

from __future__ import annotations

import functools
import glob
import os
import re

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import _parts

SCOPES = ("attn_full", "attn_window")
_SCOPE = {s: re.compile(r"/%s(/|$)" % s) for s in SCOPES}


def decode_rows(run, inside=None) -> list:
    """``(full_rows, window_rows)`` of every decode tick that began inside
    the stretch ``inside`` (default: the measured window)."""
    a, b = inside or run.window
    return [(s.attrs["full_rows"], s.attrs["window_rows"])
            for s in run.spans_named("serving.decode")
            if "full_rows" in s.attrs and a <= s.start_s <= b]


def scope_seconds(devices: dict) -> dict:
    """``{"attn_full", "attn_window", "total"}``: device self seconds,
    averaged over the devices of ``_parts.load_xplane``'s lists (kernel
    families included: a scope's time is all that ran under it)."""
    out = dict.fromkeys((*SCOPES, "total"), 0.0)
    for rows in devices.values():
        rows = _parts._named(rows)
        timed = trace_reduce.self_times(
            [[i, r[3], r[4]] for i, r in enumerate(rows)])
        for index, _, _, self_ns in timed:
            seconds = self_ns / 1e9 / len(devices)
            out["total"] += seconds
            for scope in SCOPES:
                if _SCOPE[scope].search(rows[index][1]):
                    out[scope] += seconds
    return out


@functools.lru_cache(maxsize=2)
def _of_file(path: str, mtime: float) -> dict:
    return scope_seconds(_parts.load_xplane(path))


def read_share(run, scope: str):
    """The scope's share of device self time; None without a trace or
    where no instruction carries either scope."""
    if not run.trace:
        return None
    files = glob.glob(os.path.join(harness.WORK, "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    seconds = _of_file(files[0], os.path.getmtime(files[0]))
    if not seconds["total"] or not any(seconds[s] for s in SCOPES):
        return None
    return seconds[scope] / seconds["total"]
