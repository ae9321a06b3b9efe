"""Model: share of device self time in the indexer: its projections from c_q and x, the key's LayerNorm and rotation, the gather of a lane's index keys and the scores I = sum_j w_j ReLU(qI_j . kI) over every row behind a query (scope ``dsa_index``). Grows with the context: a tick reads 256 B a cached row and lane, a chunk scores 512 x rows pairs at 16,384 operations each."""
from perfbench.layer_metrics import _dsa


def read(run):
    return _dsa.share(run, "dsa_index")
