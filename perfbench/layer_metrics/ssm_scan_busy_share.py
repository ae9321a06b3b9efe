"""Kernels: share of device self time under the scopes ``ssm_scan`` and
``ssm_step`` (the selective scan of a prefill and the one-row update of a
tick alone, with what prepares their operands; a sub-part of
``ssm_mix``)."""
from perfbench.layer_metrics import _ssm


def read(run):
    return _ssm.share(run, "scan_step")
