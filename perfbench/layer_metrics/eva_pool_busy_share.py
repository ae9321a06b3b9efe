"""Model: share of device self time under the scope ``eva_pool`` (the two
poolings of a chunk's rows, ``k~`` by ``softmax(mu . k)`` and ``v~`` by
``softmax(phi . k)``, and the pooled rows' write into the summary class, in a
prefill chunk and a tick alike: ``fleetx_tpu/models/gpt/eva.py``)."""
from perfbench.layer_metrics import _scope


def read(run):
    return _scope.share(run, "eva_pool")
