"""Kernels: share of device self time under the scope ``kda_step`` (the
one-row update of every lane's matrix state at a decode tick alone, with
what lays its operands out; a sub-part of ``kda_mix``)."""
from perfbench.layer_metrics import _kda


def read(run):
    return _kda.share(run, "step")
