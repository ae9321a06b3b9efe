"""Kernels (EVA attention's decode calls over a lane's composed table): the
least time the chip could take to read the rows the traced ticks attended
over, exact and pooled (memory-bound: bytes over peak bytes/s, by
``flops_eva.decode_tick_cost``: ``eva_window_rows + eva_summary_rows`` in
every layer, 16,384 bytes a row and layer at the published widths), over the
time the calls that read them took (the ``fleetx_decode*`` family, whatever
kernel the family holds), in percent. Rows per tick are the program's own
count on its ``serving.decode`` spans (active lanes: a free lane's
trash-page rows are not in it), averaged over the traced stretch; the ticks
traced are the family's calls over the layers."""
from perfbench import flops, flops_eva
from perfbench.layer_metrics import _eva


def read(run):
    if not run.trace or run.peaks is None or not run.traced:
        return None
    calls = run.trace["family_calls"].get("decode")
    rows = _eva.decode_rows(run, run.traced)
    if not calls or not rows:
        return None
    model = run.cell.config["model"]
    ops, bytes_ = flops_eva.decode_tick_cost(
        sum(r[0] for r in rows) / len(rows),
        sum(r[1] for r in rows) / len(rows), run.samples["lanes"], model)
    ticks = calls / model["num_layers"]
    least = flops.roofline_seconds(ops, bytes_, run.peaks)[0] * ticks
    return 100.0 * least / run.trace["family_s"]["decode"]
