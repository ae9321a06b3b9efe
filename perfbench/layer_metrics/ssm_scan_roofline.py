"""Kernels (the chunked selective scan of a prefill): the least time the
chip could take to read the scans' operands once and the state once a call
(memory-bound: ``flops_ssm.scan_cost``: ``u, dt`` in and ``y`` out in
float32, ``B, C``, 61,568 B a row and selective-scan layer at the published
widths) over the time the ``fleetx_ssm_scan*`` calls took, in percent. Rows
per prefill are the program's own count on its ``serving.admit`` and
``serving.prefill_chunk`` spans (``scan_rows``, padding included: the kernel
runs the padded rows), averaged over the traced stretch; the prefills
traced are the kernel's calls over the layers (a layer of another kind
skips the work inside the call). ``peaks.py`` has no vector-unit peak, so
the share is of the bytes' time, and this kernel is NOT bound by its bytes:
a row and layer take 81,920 exponentials, 80 cycles at one register of
1,024 a cycle, 85 ns at 940 MHz, beside 75 ns for its 61,568 bytes, and
some seven other vector operations on every one of the 80 state registers
besides. A share of 10-20% is what a kernel bound by the vector unit reads
here."""
from perfbench import flops_ssm
from perfbench.layer_metrics import _ssm


def read(run):
    if not run.trace or not run.traced:
        return None
    return _ssm.roofline(
        run, "scan", flops_ssm.scan_cost,
        _ssm.span_field(run, ("serving.admit", "serving.prefill_chunk"),
                        "scan_rows", run.traced))
