"""Model: share of device self time in attention under the selection (scope ``dsa_attn``): a tick's gather of the chosen rows of c_kv and k_r into a compact pool (the absorbed kernel ``fleetx_mla_decode_paged`` that then reads it is ``mla_decode_busy_share``'s), and a chunk's materialised attention under its mask, the kernel ``fleetx_dsa_prefill``."""
from perfbench.layer_metrics import _dsa


def read(run):
    return _dsa.share(run, "dsa_attn")
