"""Kernels (a prefill chunk's attention over grouped heads through the paged
cache, window and full layers): the least time the chip could take for what
the live steps of the traced calls of ``fleetx_prefill_gqa`` covered over the
time the kernel took, in percent. What a prefill program's calls cover (one
call a layer) is counted from the program's spans, not from the kernel's
arguments: the key rows of one full and one window layer and key head
(``attn_full_key_rows``, ``attn_window_key_rows`` on ``serving.
prefill_chunk`` and, for a prompt prefilled in one program, on
``serving.admit``) and the program's rows of queries, by
``flops_gqa_prefill.chunk_cost`` (``4 x head x query rows x heads`` a key
row: MXU-bound by far). The programs traced are the kernel's calls over the
attention layers; their mean cost is that of the programs that began inside
the traced stretch."""
from perfbench import flops, flops_gqa_prefill
from perfbench.layer_metrics import _gqa


def read(run):
    if not run.trace or not run.traced or run.peaks is None:
        return None
    took = _gqa.seconds(run)
    programs = _gqa.chunk_spans(run, run.traced)
    if not took or not took["kernel_calls"] or not programs:
        return None
    model = run.cell.config["model"]
    least = sum(flops.roofline_seconds(*flops_gqa_prefill.chunk_cost(
        *rows, model), run.peaks)[0] for rows in programs) / len(programs)
    calls = took["kernel_calls"] / model["num_layers"]
    return 100.0 * least * calls / took["kernel"]
