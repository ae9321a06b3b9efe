"""Model: share of device self time under the scope ``conv_mix`` (the gated
short-convolution operator: its two projections, its gates and its taps; a
sub-part of ``attn``, the operator's place in a layer)."""
from perfbench.layer_metrics import _lfm2


def read(run):
    return _lfm2.conv_share(run)
