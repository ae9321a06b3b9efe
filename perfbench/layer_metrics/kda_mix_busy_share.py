"""Model: share of device self time under the scope ``kda_mix`` (the KDA
operator whole: its projections, the three filters, the two low-rank gates,
the delta rule's chunk or one-row kernel, the gated per-head norm; a
sub-part of ``attn``, the operator's place in a layer)."""
from perfbench.layer_metrics import _kda


def read(run):
    return _kda.share(run, "mix")
