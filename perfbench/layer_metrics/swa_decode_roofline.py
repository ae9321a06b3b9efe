"""Kernels (paged decode attention over grouped heads, window and full
layers): the least time the chip could take to read the live cache rows of
the traced ticks (memory-bound: bytes over peak bytes/s, by
``flops_swa.decode_tick_cost``: every live row in the full layers, the
window's rows in the window layers, 2,048 bytes a row and layer at the
published widths) over the time the ``fleetx_decode*`` calls took, in
percent. Rows per tick are the program's own count on its
``serving.decode`` spans (active lanes: a free lane's trash-page rows are
not in it), averaged over the traced stretch; the ticks traced are the
kernel's calls over the layers."""
from perfbench import flops, flops_swa
from perfbench.layer_metrics import _swa


def read(run):
    if not run.trace or run.peaks is None or not run.traced:
        return None
    calls = run.trace["family_calls"].get("decode")
    rows = _swa.decode_rows(run, run.traced)
    if not calls or not rows:
        return None
    model = run.cell.config["model"]
    ops, bytes_ = flops_swa.decode_tick_cost(
        sum(r[0] for r in rows) / len(rows),
        sum(r[1] for r in rows) / len(rows), run.samples["lanes"], model)
    ticks = calls / model["num_layers"]
    least = flops.roofline_seconds(ops, bytes_, run.peaks)[0] * ticks
    return 100.0 * least / run.trace["family_s"]["decode"]
