"""Model: share of device self time in a chunk's materialised attention:
the re-expansion of the cached latents into keys and values (scope
``mla_kv_up``) and the scores, softmax and value products over them in key
blocks (``mla_attn_prefill``). Grows with the context a chunk stands
behind; what a flash path for a cached prefill would take on."""
from perfbench.layer_metrics import _mla


def read(run):
    return _mla.share(run, "mla_kv_up", "mla_attn_prefill")
