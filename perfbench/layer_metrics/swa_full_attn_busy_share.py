"""Model: share of device self time under the scope ``attn_full`` (the
attention of a full layer after its cache write: the decode kernel's call,
or a prefill chunk's gather and dense attention)."""
from perfbench.layer_metrics import _swa


def read(run):
    return _swa.read_share(run, "attn_full")
