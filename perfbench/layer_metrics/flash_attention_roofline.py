"""Kernels (flash attention): the least time the chip could take for the
traced flash calls (the larger of operations over peak FLOP/s and bytes
over peak bytes/s, from each call's own operand shapes by
``flops.flash_call_cost``) over the time they took, in percent. Every shape
here is compute-bound (see PERF.md)."""
import re

from perfbench import flops

_SHAPE3 = re.compile(r"(?:bf16|f32)\[(\d+),(\d+),(\d+)\]")
_KINDS = (("fleetx_flash_fwd", "fwd"), ("fleetx_flash_dq", "dq"),
          ("fleetx_flash_dkv", "dkv"))


def call_cost(name: str):
    """``(ops, bytes)`` of one traced flash call, from the q and k operand
    shapes ``[batch*heads, len, head_dim]`` in its instruction text."""
    kind = next((k for mark, k in _KINDS if mark in name), None)
    operands = name.split("custom-call(", 1)[-1]
    shapes = _SHAPE3.findall(operands)
    if kind is None or len(shapes) < 2:
        return None
    (bh, q_len, d), (_, kv_len, _) = (tuple(map(int, s)) for s in shapes[:2])
    return flops.flash_call_cost(kind, 1, bh, q_len, kv_len, d, causal=True)


def read(run):
    if not run.trace or run.peaks is None:
        return None
    least = took = 0.0
    for family, name, self_s in run.trace["kernel_events"]:
        cost = call_cost(name) if family == "flash" else None
        if cost is not None:
            least += flops.roofline_seconds(*cost, run.peaks)[0]
            took += self_s
    return 100.0 * least / took if took else None
