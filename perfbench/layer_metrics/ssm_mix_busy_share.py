"""Model: share of device self time under the scope ``ssm_mix`` (the Mamba
mixer whole: its projections, filter, inner norms, the scan or the one-row
update, the gate; a sub-part of ``attn``, the operator's place in a
layer)."""
from perfbench.layer_metrics import _ssm


def read(run):
    return _ssm.share(run, "mix")
