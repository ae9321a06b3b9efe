"""Model: share of device self time under the scopes ``moe_zero`` (the
chosen zero-compute experts' weights times the layer's input,
``fleetx_tpu/parallel/moe_share.py``) and ``moe_shortcut`` (the add with
which the expert layer's output lands on the stream a half later,
``fleetx_tpu/models/gpt/mixed_stack.py``): what the two mechanisms cost
beside the matrix products they save. Read from the same trace file and by
the same wire-format reader as ``_parts.py``, whose rules book both to
``mlp``. None without a trace, and for a program with neither scope."""

from __future__ import annotations

import functools
import glob
import os
import re

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import _parts

_SCOPES = re.compile(r"/(moe_zero|moe_shortcut)(/|$)")


@functools.lru_cache(maxsize=2)
def _share(path: str, mtime: float):
    devices = _parts.load_xplane(path)
    total = under = 0.0
    for rows in devices.values():
        rows = _parts._named(rows)
        for index, _, _, self_ns in trace_reduce.self_times(
                [[i, r[3], r[4]] for i, r in enumerate(rows)]):
            total += self_ns
            under += self_ns * bool(_SCOPES.search(rows[index][1]))
    return under / total if total and under else None


def read(run):
    if not run.trace:
        return None
    files = glob.glob(os.path.join(harness.WORK, "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return _share(files[0], os.path.getmtime(files[0])) if files else None
