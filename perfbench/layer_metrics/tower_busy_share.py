"""Model (the vision tower): share of device self time in the tower's programs (scopes ``tower`` and, inside it, ``vit_embed``, ``vit_attn``, ``vit_mlp``, ``vit_project``: one program an image, in the step's prefill slot), which compete with chunks and ticks for the one chip. Nothing where a trie hit spares every image."""
from perfbench.layer_metrics import _vl


def read(run):
    return _vl.share(run, "tower")
