"""Model: share of device self time in latent attention's products with its
weights: the low-rank query and key/value projections, their norms, the
rotation and the output projection (scope ``mla_proj``), and in a tick the
absorbed products with ``W_UK`` and ``W_UV`` (``mla_absorb``)."""
from perfbench.layer_metrics import _mla


def read(run):
    return _mla.share(run, "mla_proj", "mla_absorb")
