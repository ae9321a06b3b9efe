"""Model: share of device self time under the scope ``attn_gate`` (the
attention's output gate: its projection ``a W_g`` as wide as the queries',
the sigmoid and the product with the heads' output, in a prefill chunk and a
tick alike)."""
from perfbench.layer_metrics import _gqa


def read(run):
    return _gqa.share(run, "attn_gate")
