"""Kernels (a tick's grouped attention under the indexer's selection): the
least time the chip could take for WHAT THE TRACED TICKS NEEDED over the
time their attention under the selection took, in percent. What a call (one
layer of one tick) needs is counted from the program's spans: the chosen
rows of the lanes it decodes (``selected_rows`` on ``serving.decode``), each
row's key and value read once from the pool and scored by every query head
(``flops_dsa_gqa.sparse_decode_cost``: 2,048 B and ``32 x 256 x 2``
operations a row at the published widths). The time is every instruction
under ``dsa_attn`` of the tick's program (``_vl.seconds_of``'s
``tick_attn``): the GATHER of the chosen rows into a compact pool and the
kernel ``fleetx_decode_paged`` over it. The kernel's own events alone would
read above 100%: the compiler keeps the compact pool in VMEM (the gather's
output carries ``S(1)`` in the compiled tick), so the one read of HBM is the
gather's."""
from perfbench import flops, flops_dsa_gqa
from perfbench.layer_metrics import _dsa, _vl


def read(run):
    if not run.trace or not run.traced or run.peaks is None:
        return None
    took = _vl.seconds(run)
    rows = _dsa.span_field(run, ("serving.decode",), "selected_rows",
                           run.traced)
    if not took or not took["tick_calls"] or not rows:
        return None
    ops, bytes_ = flops_dsa_gqa.sparse_decode_cost(
        sum(rows) / len(rows), run.cell.config["model"])
    least = (flops.roofline_seconds(ops, bytes_, run.peaks)[0]
             * took["tick_calls"])
    return 100.0 * least / took["tick_attn"]
