"""What the readers of a stack with two kinds of state share: the
``serving.decode`` spans that carry the live rows ONE attention layer reads
at a tick (``attn_rows``) and the ``serving.admit`` spans that carry the
tokens the trie matched and whether the convolution state was resumed from
the matched pages (``matched``, ``state_resumed``:
``fleetx_tpu/serving/engine.py`` sets them for a model that keeps such
state), and device self time under the scope ``conv_mix``
(``fleetx_tpu/models/gpt/mixed_stack.py``), read from the same trace file
and by the same wire-format reader as ``_parts.py``, whose rules book it to
``attn``. Empty for a program that has no such span field or scope (a parent
commit's, another configuration's)."""

from __future__ import annotations

import functools
import glob
import os
import re

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import _parts

_CONV = re.compile(r"/conv_mix(/|$)")


def decode_rows(run, inside=None) -> list:
    """``attn_rows`` of every decode tick that began inside the stretch
    ``inside`` (default: the measured window)."""
    a, b = inside or run.window
    return [s.attrs["attn_rows"] for s in run.spans_named("serving.decode")
            if "attn_rows" in s.attrs and a <= s.start_s <= b]


def admissions(run) -> list:
    """``(prompt tokens, tokens matched, state resumed)`` of every
    admission that began inside the measured window."""
    a, b = run.window
    return [(s.attrs["prompt_len"], s.attrs["matched"],
             s.attrs["state_resumed"])
            for s in run.spans_named("serving.admit")
            if "matched" in s.attrs and a <= s.start_s <= b]


def scope_seconds(devices: dict) -> dict:
    """``{"conv_mix", "total"}``: device self seconds, averaged over the
    devices of ``_parts.load_xplane``'s lists."""
    out = {"conv_mix": 0.0, "total": 0.0}
    for rows in devices.values():
        rows = _parts._named(rows)
        timed = trace_reduce.self_times(
            [[i, r[3], r[4]] for i, r in enumerate(rows)])
        for index, _, _, self_ns in timed:
            seconds = self_ns / 1e9 / len(devices)
            out["total"] += seconds
            if _CONV.search(rows[index][1]):
                out["conv_mix"] += seconds
    return out


@functools.lru_cache(maxsize=2)
def _of_file(path: str, mtime: float) -> dict:
    return scope_seconds(_parts.load_xplane(path))


def conv_share(run):
    """The scope's share of device self time; None without a trace or where
    no instruction carries the scope."""
    if not run.trace:
        return None
    files = glob.glob(os.path.join(harness.WORK, "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    seconds = _of_file(files[0], os.path.getmtime(files[0]))
    if not seconds["total"] or not seconds["conv_mix"]:
        return None
    return seconds["conv_mix"] / seconds["total"]
