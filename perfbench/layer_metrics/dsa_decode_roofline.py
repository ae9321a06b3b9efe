"""Kernels (a tick's attention under the indexer's selection): the least
time the chip could take for the traced calls of ``fleetx_mla_decode_paged``
over the time they took, in percent, where the kernel reads the COMPACT pool
of the rows the indexer kept (``models/gpt/latent.py`` ``_sparse_decode``)
and not every live row. A call (one layer of one tick) reads each chosen row
once for all heads (1,280 B) and spends ``heads x (576 + 512) x 2``
operations on it (``flops_dsa.sparse_decode_cost``); the chosen rows of a
call are the program's own count on its ``serving.decode`` spans
(``selected_rows``), averaged over the traced stretch. (``mla_decode_
roofline`` counts ``latent_rows``, every live row, which this program does
not read: the cell is not on its list.)"""
from perfbench import flops, flops_dsa
from perfbench.layer_metrics import _mla


def read(run):
    if not run.trace or not run.traced or run.peaks is None:
        return None
    took = _mla.seconds(run)
    rows = _mla.span_field(run, ("serving.decode",), "selected_rows",
                           run.traced)
    if not took or not took["kernel_calls"] or not rows:
        return None
    ops, bytes_ = flops_dsa.sparse_decode_cost(sum(rows) / len(rows),
                                               run.cell.config["model"])
    least = (flops.roofline_seconds(ops, bytes_, run.peaks)[0]
             * took["kernel_calls"])
    return 100.0 * least / took["kernel"]
