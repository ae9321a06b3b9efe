"""Model: share of device self time under the scope ``attn_window`` (the
attention of a window layer after its cache write: the decode kernel's call,
or a prefill chunk's gather and dense attention)."""
from perfbench.layer_metrics import _swa


def read(run):
    return _swa.read_share(run, "attn_window")
