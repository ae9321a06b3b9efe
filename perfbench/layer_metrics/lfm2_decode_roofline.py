"""Kernels (paged decode attention over grouped heads, in a stack where only
some layers are attention): the least time the chip could take to read the
live cache rows of the traced ticks (memory-bound: bytes over peak bytes/s,
by ``flops_lfm2.decode_tick_cost``: every live row in each ATTENTION layer,
2,048 bytes a row and layer at the published widths, and none in a
convolution layer) over the time the ``fleetx_decode*`` calls took, in
percent. Rows per tick are the program's own count on its ``serving.decode``
spans (``attn_rows``, active lanes: a free lane's trash-page rows are not in
it), averaged over the traced stretch; the ticks traced are the kernel's
calls over the attention layers."""
from perfbench import flops, flops_lfm2
from perfbench.layer_metrics import _lfm2


def read(run):
    if not run.trace or run.peaks is None or not run.traced:
        return None
    calls = run.trace["family_calls"].get("decode")
    rows = _lfm2.decode_rows(run, run.traced)
    model = run.cell.config["model"]
    if not calls or not rows or "layer_types" not in model:
        return None
    ops, bytes_ = flops_lfm2.decode_tick_cost(
        sum(rows) / len(rows), run.samples["lanes"], model)
    ticks = calls / flops_lfm2.layer_counts(model)[0]
    least = flops.roofline_seconds(ops, bytes_, run.peaks)[0] * ticks
    return 100.0 * least / run.trace["family_s"]["decode"]
