"""Kernels (a chunk's grouped attention under the indexer's selection): the
least time the chip could take for WHAT THE TRACED CALLS NEEDED over the time
the kernel ``fleetx_gqa_sparse_prefill`` took, in percent. What a call (one
layer of one prefill program) needs is counted from the program's spans, not
from the kernel's arguments: the (query, chosen row) pairs of the traced
prefill calls (``selected_rows`` on ``serving.prefill_chunk`` and, for a
prompt prefilled in one program, on ``serving.admit``), each scored and
summed by every query head (``flops_dsa_gqa.sparse_chunk_cost``: ``32 x 2 x
128 x 2`` operations a pair, a floor of what any form needs), as
``dsa_prefill_roofline`` counts its own. Today's kernel visits every key
block up to the chunk's last row: 16 times the pairs at a context of 32k.
The share says how far a kernel that visits the chosen rows alone could
go."""
from perfbench import flops, flops_dsa_gqa
from perfbench.layer_metrics import _dsa, _vl


def read(run):
    if not run.trace or not run.traced or run.peaks is None:
        return None
    took = _vl.seconds(run)
    pairs = _dsa.span_field(
        run, ("serving.prefill_chunk", "serving.admit"), "selected_rows",
        run.traced)
    if not took or not took["kernel_calls"] or not pairs:
        return None
    ops, bytes_ = flops_dsa_gqa.sparse_chunk_cost(
        sum(pairs) / len(pairs), run.cell.config["model"])
    least = (flops.roofline_seconds(ops, bytes_, run.peaks)[0]
             * took["kernel_calls"])
    return 100.0 * least / took["kernel"]
