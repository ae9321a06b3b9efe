"""Model: share of device self time under the scope ``mla_qk_norm`` (a
latent layer's per-head norm of its queries and the norm of the rotary key
all heads share, before the rotation, in a prefill chunk and a tick alike:
``fleetx_tpu/models/gpt/latent.py``; a sub-part of ``mla_proj``)."""
from perfbench.layer_metrics import _scope


def read(run):
    return _scope.share(run, "mla_qk_norm")
