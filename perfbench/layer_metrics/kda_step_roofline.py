"""Kernels (the one-row update of the lane-resident matrix state at a
decode tick): the least time the chip could take to read and write ``S`` of
the lanes that were decoding with their rows' operands
(``flops_kda.step_cost``: 2 x 4,194,304 B a lane and KDA layer at the
published widths; its 7.3 MFLOP a lane and layer are 37 ns at the chip's
peak beside 10 us for the bytes, so the bytes bound it) over the time the
``fleetx_kda_step*`` calls took, in percent. Lanes per tick are the
program's own count on its ``serving.decode`` spans (``state_lanes``),
averaged over the traced stretch; the ticks traced are the kernel's calls
over the layers (in a layer of another kind the call moves one block)."""
from perfbench import flops_kda
from perfbench.layer_metrics import _kda


def read(run):
    if not run.trace or not run.traced:
        return None
    return _kda.roofline(
        run, "step_kernel", flops_kda.step_cost,
        _kda.span_field(run, ("serving.decode",), "state_lanes", run.traced))
