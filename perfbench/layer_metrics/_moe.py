"""Device self time under the expert layer's two scopes, ``moe_experts``
(the grouped matmuls) and ``moe_route`` (router, softmax, top-k, row
layout, gather, weighted sum), which ``fleetx_tpu/parallel/moe.py`` sets
inside the module path ``layer/moe_mlp``. Read from the same trace file
and by the same wire-format reader as ``_parts.py``, whose rules book both
to ``mlp``. Empty for a run without a trace; zeros for a program that has
no such scope (a parent commit's)."""

from __future__ import annotations

import functools
import glob
import os
import re

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import _parts

SCOPES = ("moe_experts", "moe_route")
_SCOPE = {s: re.compile(r"/%s(/|$)" % s) for s in SCOPES}


def scope_seconds(devices: dict) -> dict:
    """``{"moe_experts", "moe_route", "total"}``: device self seconds,
    averaged over the devices of ``_parts.load_xplane``'s lists."""
    out = dict.fromkeys((*SCOPES, "total"), 0.0)
    for rows in devices.values():
        rows = _parts._named(rows)
        timed = trace_reduce.self_times(
            [[i, r[3], r[4]] for i, r in enumerate(rows)])
        for index, _, _, self_ns in timed:
            seconds = self_ns / 1e9 / len(devices)
            out["total"] += seconds
            # a nested scope: the innermost (last) one named wins
            found = [(m.start(), s) for s in SCOPES
                     for m in _SCOPE[s].finditer(rows[index][1])]
            if found:
                out[max(found)[1]] += seconds
    return out


@functools.lru_cache(maxsize=2)
def _of_file(path: str, mtime: float) -> dict:
    return scope_seconds(_parts.load_xplane(path))


def traced_seconds(run) -> dict:
    if not run.trace:
        return {}
    files = glob.glob(os.path.join(harness.WORK, "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return _of_file(files[0], os.path.getmtime(files[0])) if files else {}


def read_share(run, scope: str):
    seconds = traced_seconds(run)
    if not seconds or not seconds["total"]:
        return None
    return seconds[scope] / seconds["total"]
