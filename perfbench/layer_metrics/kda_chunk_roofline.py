"""Kernels (the delta rule of a prefill call): the least time the chip
could take for the calls' work AS THE RECURRENCE COUNTS IT
(``flops_kda.chunk_cost``: ``7 x 128 x 128`` operations a head and row at
the chip's peak, or the rows' ``q, k, v, g, beta, o`` and the lane's ``S``
read and written once at 819 GB/s, whichever is greater: the same work
whatever form the kernel takes) over the time the ``fleetx_kda_chunk*``
calls took, in percent. Rows per call are the program's own count on its
``serving.admit`` and ``serving.prefill_chunk`` spans (``scan_rows``, padding
included: the kernel runs the padded rows), averaged over the traced
stretch; the calls traced are the kernel's over the layers (a layer of
another kind skips the work inside the call). The peak is the MXU's, and a
kernel that runs the recurrence row by row on the vector unit reads a few
percent at most: that distance is what a chunkwise form would close."""
from perfbench import flops_kda
from perfbench.layer_metrics import _kda


def read(run):
    if not run.trace or not run.traced:
        return None
    return _kda.roofline(
        run, "chunk_kernel", flops_kda.chunk_cost,
        _kda.span_field(run, ("serving.admit", "serving.prefill_chunk"),
                        "scan_rows", run.traced))
