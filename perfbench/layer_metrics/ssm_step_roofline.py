"""Kernels (the one-row update of the lane-resident state at a decode tick):
the least time the chip could take to read and write ``h`` of the lanes
that were decoding (memory-bound: ``flops_ssm.step_cost``, 2 x 327,680 B a
lane and selective-scan layer at the published widths: what the kernel
itself moves; the filter's rows, 2 x 30,720 B more, move under
``cache_write/ssm_state`` and are in neither side of this share) over the
time the ``fleetx_ssm_step*`` calls took, in percent. Lanes per tick are the
program's own count on its ``serving.decode`` spans (``state_lanes``),
averaged over the traced stretch; the ticks traced are the kernel's calls
over the layers (the layer loop calls it in every layer; in a layer of
another kind it moves the same bytes for nothing, which the share then
shows). ``peaks.py`` has no vector-unit peak, so the share is of the bytes'
time: a lane and layer take 81,920 exponentials and some 570,000 other
vector operations for 655,360 bytes, 0.80 us at 819 GB/s; at one register
of 1,024 exponentials a cycle they are 80 cycles, a tenth of that, so the
bytes bound this kernel first."""
from perfbench import flops_ssm
from perfbench.layer_metrics import _ssm


def read(run):
    if not run.trace or not run.traced:
        return None
    return _ssm.roofline(
        run, "step", flops_ssm.step_cost,
        _ssm.span_field(run, ("serving.decode",), "state_lanes", run.traced))
