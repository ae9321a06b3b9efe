"""Model: share of device self time in the selection: the exact top ``index_topk`` of a query's index scores (scope ``dsa_select``): a tick's ``lax.top_k`` and sort a lane, a chunk's search for each row's k-th score (32 counts over [rows, cache rows]) and its ties. Plain XLA: it has a busy share and no roofline."""
from perfbench.layer_metrics import _dsa


def read(run):
    return _dsa.share(run, "dsa_select")
