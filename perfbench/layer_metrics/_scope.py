"""Share of device self time under ONE named scope, for a reader that needs
no more than that: read from the same trace file and by the same wire-format
reader as ``_parts.py``. None for a program no instruction of which carries
the scope (a parent commit's, another configuration's)."""

from __future__ import annotations

import functools
import glob
import os
import re

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import _parts


def share_of(devices: dict, scope: str):
    """Self seconds under ``scope`` over all self seconds, over the devices
    of ``_parts.load_xplane``'s lists; None where nothing carries it."""
    found, under, total = re.compile(r"/%s(/|$)" % re.escape(scope)), 0.0, 0.0
    for rows in devices.values():
        rows = _parts._named(rows)
        for index, _, _, self_ns in trace_reduce.self_times(
                [[i, r[3], r[4]] for i, r in enumerate(rows)]):
            total += self_ns
            under += self_ns if found.search(rows[index][1]) else 0.0
    return under / total if under and total else None


@functools.lru_cache(maxsize=4)
def _of_file(path: str, mtime: float, scope: str):
    return share_of(_parts.load_xplane(path), scope)


def share(run, scope: str):
    """:func:`share_of` the run's trace; None without one."""
    if not run.trace:
        return None
    files = glob.glob(os.path.join(harness.WORK, "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    return _of_file(files[0], os.path.getmtime(files[0]), scope)
