"""Kernels (the absorbed decode over the latent pool): the least time the
chip could take for the traced calls of ``fleetx_mla_decode_paged`` over the
time they took, in percent. A call (one layer of one tick) reads every live
cached row of every lane ONCE, as the pool's two leaves hold it (1,280 B at
the published widths), and spends ``heads x (576 + 512) x 2`` operations on
it (``flops_mla.decode_cost``): 109 FLOP a byte as held, under the v5e's
ridge of 240, so the bytes bound it, by a factor of two. The live rows of a
call are the program's own count on its ``serving.decode`` spans
(``latent_rows``), averaged over the traced stretch; the calls are the
kernel's in the trace."""
from perfbench import flops, flops_mla
from perfbench.layer_metrics import _mla


def read(run):
    if not run.trace or not run.traced or run.peaks is None:
        return None
    took = _mla.seconds(run)
    rows = _mla.span_field(run, ("serving.decode",), "latent_rows",
                           run.traced)
    if not took or not took["kernel_calls"] or not rows:
        return None
    ops, bytes_ = flops_mla.decode_cost(sum(rows) / len(rows),
                                        run.cell.config["model"])
    least = (flops.roofline_seconds(ops, bytes_, run.peaks)[0]
             * took["kernel_calls"])
    return 100.0 * least / took["kernel"]
