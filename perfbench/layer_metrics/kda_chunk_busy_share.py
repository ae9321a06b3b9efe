"""Kernels: share of device self time under the scope ``kda_chunk`` (the
delta rule of a prefill call alone, with what lays its operands out; a
sub-part of ``kda_mix``)."""
from perfbench.layer_metrics import _kda


def read(run):
    return _kda.share(run, "chunk")
