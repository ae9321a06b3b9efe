"""A lane that decodes no token is handed an EMPTY window, by one rule
(``paged_write.decode_end``: the lane's row went to the trash page), in the
three stacks that build a decode kernel's window: ``GPTModel``'s paged
branch (models/gpt/model.py), the grouped-head stack with full and window
layers under a ``[class, lanes, pages]`` table (models/gpt/hybrid.py), and
latent attention (models/gpt/latent.py). One tick of four lanes at the
model's own ``apply``, tables and positions written out by hand: two busy
lanes, a free lane (zeroed table, write pinned to the last row), and a lane
that is inactive in this tick but OWNS a real page at the last row (parked,
or mid-prefill with a request that fills the row), which attends as ever.
The kernels run interpreted (``FLEETX_FORCE_FLASH=1``)."""

import dataclasses

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt import paged_write
from fleetx_tpu.models.gpt.generation import init_decode_cache
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.ops.pallas import decode_attention, mla_decode

PAGE, CACHE_LEN, PAGES = 8, 64, 40
ROW = CACHE_LEN // PAGE
COMMON = dict(vocab_size=128, hidden_size=64, max_position_embeddings=256,
              hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
              use_flash_attention=True)
# name -> (GPTConfig.from_model_config fields, the kernel entry its tick
# calls as (module, name), attention layers, classes of page)
STACKS = {
    "gpt": (dict(COMMON, num_layers=2, num_attention_heads=4,
                 ffn_hidden_size=128, dtype="float32"),
            (decode_attention, "flash_decode_paged_attention"), 2, 1),
    "hybrid": (dict(COMMON, num_layers=4, num_attention_heads=8,
                    num_key_value_heads=2, head_size=16, ffn_hidden_size=32,
                    num_experts=8, gate="softmax_topk", top_k=2,
                    norm_topk_prob=True, position_embedding="rope",
                    rope_layout=(0, 1, 1, 1), sliding_window=16,
                    sliding_window_layout=(0, 1, 1, 1), norm="rmsnorm",
                    mlp_act="reglu", use_bias=False,
                    tie_word_embeddings=False, router_input="block_input",
                    expert_mode=True, family="smallthinker",
                    dtype="float32"),
               (decode_attention, "flash_decode_paged_attention"), 4, 2),
    "latent": (dict(COMMON, num_layers=3, num_attention_heads=4,
                    ffn_hidden_size=32, position_embedding="rope",
                    norm="rmsnorm", mlp_act="swiglu", use_bias=False,
                    tie_word_embeddings=False,
                    layer_types=["latent_attention"] * 3, num_dense_layers=3,
                    dense_ffn_hidden_size=96, q_lora_rank=24, kv_lora_rank=32,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    dtype="float32"),
               (mla_decode, "mla_decode_paged"), 3, 1),
}


def _served(fields, classes):
    cfg = GPTConfig.from_model_config(fields)
    model = GPTForPretraining(cfg)
    params = flax.core.meta.unbox(jax.jit(lambda k: model.init(
        k, np.zeros((1, 8), np.int32)))(jax.random.PRNGKey(0)))["params"]
    paged = dict(decode_cache_len=CACHE_LEN, decode_num_pages=PAGES,
                 decode_page_size=PAGE)
    if classes == 2:
        paged["decode_window_pages"] = PAGES
    return model.clone(cfg=dataclasses.replace(cfg, **paged)), params


def _forward(model, params, cache, ids, at, tables):
    def run(params, cache, ids, at, tables):
        pos = at[:, None] + jnp.arange(ids.shape[1])[None]
        logits, mut = model.apply(
            {"params": params, "cache": cache}, ids, pos, None, decode=True,
            cache_positions=at, block_tables=tables, mutable=["cache"])
        return logits, mut["cache"]

    return jax.jit(run)(params, cache, ids, at, tables)


@pytest.mark.parametrize("stack", list(STACKS))
def test_a_lane_on_the_trash_page_has_an_empty_window(monkeypatch, stack):
    """The ``end`` that reaches the kernel, in every attention layer, is
    ``wpos + 1`` for the busy lanes, 0 for the lane on the trash page and
    the row's length for the inactive lane that owns its last page; and the
    busy lanes' logits equal, bit for bit, what the parent's rule (``wpos +
    1`` for every lane) gives them."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    fields, (module, entry), layers, classes = STACKS[stack]
    model, params = _served(fields, classes)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, 128, (2, 24), dtype=np.int32)
    lengths = (21, 10)

    def classed(table):   # a class of page has numbers of its own
        table = np.asarray(table, np.int32)
        if classes == 1:
            return jnp.asarray(table)
        return jnp.asarray(np.stack([table, np.where(table, table + 3, 0)]))

    # lane 0 holds pages 1-8 and lane 1 pages 9-16: prefilled one by one
    own = np.zeros((4, ROW), np.int32)
    own[0], own[1] = np.arange(1, 9), np.arange(9, 17)
    own[3, -1] = 20     # inactive here, and the owner of a real last page
    cache = init_decode_cache(model, 4)
    for lane, n in enumerate(lengths):
        _, cache = _forward(model, params, cache,
                            jnp.asarray(tokens[lane:lane + 1, :n]),
                            jnp.asarray([0]),
                            classed(own)[..., lane:lane + 1, :])

    seen = []
    real = getattr(module, entry)

    def recording(*args, end, **kwargs):
        jax.debug.callback(lambda e: seen.append(np.asarray(e)), end)
        return real(*args, end=end, **kwargs)

    monkeypatch.setattr(module, entry, recording)
    ids = jnp.asarray([[tokens[0, 21]], [tokens[1, 10]], [0], [0]])
    at = jnp.asarray(lengths + (CACHE_LEN - 1, CACHE_LEN - 1))
    logits, _ = _forward(model, params, cache, ids, at, classed(own))
    jax.effects_barrier()
    assert len(seen) >= layers   # every call the tick makes of the entry
    for end in seen:
        np.testing.assert_array_equal(end, [22, 11, 0, CACHE_LEN])

    monkeypatch.setattr(paged_write, "decode_end",
                        lambda tables, wpos, page_size: wpos + 1)
    seen.clear()
    parents, _ = _forward(model, params, cache, ids, at, classed(own))
    jax.effects_barrier()
    for end in seen:
        np.testing.assert_array_equal(end, [22, 11, CACHE_LEN, CACHE_LEN])
    np.testing.assert_array_equal(np.asarray(logits[:2]),
                                  np.asarray(parents[:2]))
    assert np.isfinite(np.asarray(logits)).all()
