"""A prefill writes its keys and values a page at a time (PERF.md, PR 39).

``models/gpt/paged_write.py`` is THE write of a call's rows into the page
pool. Where the call is one sequence over a whole number of pages it goes
through the block table by page; everything else keeps the row form.

- **Same bits**: the page form against the row form as it stood before
  (written down here, :func:`row_form`), every pool equal bit for bit
  outside the trash page, over layouts x dtypes x ``keep`` x where the span
  lies, offsets that are NOT page-aligned among them.
- **Same text**: a decode tick's and a verify's shapes lower to the row
  form's program text, operation for operation.
- **Through the model**: an engine whose predicate is patched to the row
  form holds the same cache and returns the same tokens (the carried stack
  with ``layer_index``, bfloat16 and int8 with its scale pools).
- **On the TPU's lowering**: a 768-row prefill of the docs-batch shapes
  holds no scatter with 768 indices under ``cache_write``.
"""

import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import sharing_programs

from fleetx_tpu.models.gpt import paged_write
from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.serving import ServingEngine

PAGES, PS, CACHE_LEN, LAYERS, LAYER = 9, 8, 48, 3, 1
WIDTH, HEADS = 16, 2


def row_form(pools, rows, tables, wpos, max_len, keep=None):
    """The write as ``_update_paged_cache`` and ``hybrid.write_rows`` both
    held it before PR 39: one index pair and one update a row."""
    ps = pools[0].shape[1]
    s = rows[0].shape[0] // wpos.shape[0]
    with jax.named_scope("cache_write"):
        pos = wpos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        pos = jnp.minimum(pos, max_len - 1)
        page = jnp.take_along_axis(tables, pos // ps, axis=1)
        page, off = page.reshape(-1), (pos % ps).reshape(-1)
        if keep is not None:
            page = jnp.where(keep, page, pools[0].shape[0])
        mode = None if keep is None else "drop"
        return [pool.at[page, off].set(new, mode=mode)
                for pool, new in zip(pools, rows)]


# ----------------------------------------------------------------- same bits

# where the span lies: (rows, write offset, the lane's table)
SPANS = {
    "offset_0": (16, 0, [3, 5, 7, 2, 0, 0]),
    "after_a_shared_prefix": (16, 16, [3, 5, 7, 2, 0, 0]),
    "tail_into_zeroed_entries": (32, 8, [3, 5, 7, 0, 0, 0]),
    "ends_at_cache_len": (16, 32, [3, 5, 7, 2, 4, 6]),
    "one_page": (8, 24, [3, 5, 7, 2, 0, 0]),
    "not_aligned": (16, 11, [3, 5, 7, 2, 0, 0]),
    "not_aligned_tail_into_zeroed_entries": (24, 13, [3, 5, 7, 0, 0, 0]),
    "not_aligned_to_the_last_page": (16, 29, [3, 5, 7, 2, 4, 6]),
}
LAYOUTS = {"own_pool": (1, 0), "carried_stack": (LAYERS, LAYER)}
KEEPS = {"keep_none": None, "keep_true": True, "keep_false": False}
CASES = list(itertools.product(LAYOUTS, ("bfloat16", "int8"), KEEPS, SPANS))


def _operands(layers, dtype, rows, seed=0):
    """Pools full of values (so a row that must stay is seen to stay) and
    the call's rows: keys and values, and under int8 their scale pools."""
    rng = np.random.default_rng(seed)
    specs = ([(jnp.int8, WIDTH)] * 2 + [(jnp.float32, HEADS)] * 2
             if dtype == "int8" else [(jnp.bfloat16, WIDTH)] * 2)

    def draw(shape, kind):
        if kind == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(shape), kind)

    pools = [draw((layers * PAGES, PS, width), kind) for kind, width in specs]
    new = [draw((rows, width), kind) for kind, width in specs]
    return pools, new


def _written_by(write):
    """One jitted program a form: where the span lies (offset, table, the
    mask's value) is an operand, so the 96 cases compile the 32 shapes of
    call they are (layout, dtype, masked or not, rows) and not one each."""
    return jax.jit(lambda pools, new, tables, wpos, *keep: write(
        pools, new, tables, wpos, CACHE_LEN, *keep))


_PAGE_FORM = _written_by(paged_write.write_rows)
_ROW_FORM = _written_by(row_form)


@pytest.mark.parametrize("layout,dtype,keep,span", CASES,
                         ids=["-".join(c) for c in CASES])
def test_page_form_leaves_the_row_forms_bits(layout, dtype, keep, span):
    layers, layer = LAYOUTS[layout]
    rows, offset, table = SPANS[span]
    assert paged_write.page_writes(1, rows, PS) == rows // PS
    pools, new = _operands(layers, dtype, rows)
    # a layer's own page numbers: its base added, its trash page the base
    tables = jnp.asarray([table], jnp.int32) + layer * PAGES
    wpos = jnp.asarray([offset], jnp.int32)
    keep = KEEPS[keep]
    args = (pools, new, tables, wpos) + (() if keep is None
                                         else (jnp.asarray(keep),))

    got, want = _PAGE_FORM(*args), _ROW_FORM(*args)
    trash = layer * PAGES
    for before, a, b in zip(pools, got, want):
        a, b = (np.delete(np.asarray(x.astype(jnp.float32)), trash, axis=0)
                for x in (a, b))
        np.testing.assert_array_equal(a, b)
        untouched = np.delete(
            np.asarray(before.astype(jnp.float32)), trash, axis=0)
        assert np.array_equal(a, untouched) == (keep is False)


# ----------------------------------------------------------------- same text

@pytest.mark.parametrize("batch,rows,keep", [
    (4, 1, None), (1, 1, None), (4, 4, None), (1, 4, None), (2, 16, None),
    (1, 12, None), (4, 1, True)],
    ids=["tick", "tick_one_lane", "verify_k3", "verify_one_lane",
         "two_sequences_whole_pages", "one_sequence_no_whole_pages",
         "tick_keep"])
def test_other_shapes_lower_to_the_row_forms_text(batch, rows, keep):
    assert paged_write.page_writes(batch, rows, PS) == 0
    pools, new = _operands(LAYERS, "int8", batch * rows)
    tables = jnp.zeros((batch, CACHE_LEN // PS), jnp.int32)
    wpos = jnp.zeros((batch,), jnp.int32)
    keep = () if keep is None else (jnp.asarray(keep),)

    def text(form):
        def write(pools, new, tables, wpos, *keep):
            return form(pools, new, tables, wpos, CACHE_LEN, *keep)
        return jax.jit(write).lower(pools, new, tables, wpos, *keep).as_text()

    assert text(paged_write.write_rows) == text(row_form)
    assert "stablehlo.scatter" in text(row_form)


# --------------------------------------------------------- through the model

@pytest.fixture(scope="module")
def tiny():
    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=2, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=1024,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    model = GPTForPretraining(cfg)
    return model, jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))


@sharing_programs
def _engine(tiny, **kwargs):
    model, params = tiny
    return ServingEngine(
        model, params, slots=2, prefill_bucket=8,
        gen_cfg=GenerationConfig(decode_strategy="greedy",
                                 eos_token_id=10**6, pad_token_id=60),
        **kwargs)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("chunk", [0, 16], ids=["one_call", "chunks"])
def test_an_engine_on_the_row_form_holds_the_same_cache(
        tiny, monkeypatch, kv_dtype, chunk):
    """Two prompts that share two pages, the second admitted behind the
    prefix (a write offset of whole pages), then ticks: the cache of every
    layer and the tokens, page form against the same engine on the row
    form."""
    rng = np.random.default_rng(7)
    first = rng.integers(1, 60, 29, dtype=np.int32)
    second = np.concatenate([first[:16], rng.integers(1, 60, 9, np.int32)])

    def serve():   # (traced anew: the second under the patch below)
        eng = _engine.__wrapped__(tiny, cache_len=64, page_size=8,
                                  kv_dtype=kv_dtype, prefill_chunk=chunk)
        ids = [eng.submit(p, max_length=5) for p in (first, second)]
        done = eng.drain()
        snap = eng.metrics.snapshot()
        return ([list(done[i].tokens) for i in ids], snap,
                jax.tree.map(np.asarray, eng.cache_manager.cache))

    tokens, snap, cache = serve()
    assert snap["prefill_page_writes"] > 0
    assert snap["prefill_row_writes"] == 0
    assert snap["prefill_tokens_saved"] == 16
    monkeypatch.setattr(paged_write, "page_writes", lambda *shape: 0)
    row_tokens, row_snap, row_cache = serve()
    assert row_snap["prefill_row_writes"] == snap["prefill_page_writes"]
    assert row_snap["prefill_page_writes"] == 0
    assert tokens == row_tokens

    def beside_trash(leaf):  # [layers, pages, ps, w]: page 0 is the trash
        return leaf[:, 1:] if leaf.ndim == 4 else leaf

    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        beside_trash(a), beside_trash(b)), cache, row_cache)


# ------------------------------------------------- on the TPU's own lowering

def _scatter_index_counts(text):
    """Number of indices of every ``stablehlo.scatter`` in a lowered text
    (the leading extent of its second operand's type)."""
    types = re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \(([^()]*)\) ->', text, re.S)
    return [int(re.findall(r"tensor<([^>]*)>", t)[1].split("x")[0])
            for t in types]


def test_a_768_row_prefill_scatters_pages_not_rows(tiny):
    """The docs-batch shapes (a lane of 1,024 tokens, pages of 16, a bucket
    of 768 rows), lowered for the TPU without one: the prefill program's
    scatters take one index a page of the span (48 and the one the span
    may run into), where the row form took 768."""
    eng = _engine(tiny, cache_len=1024, page_size=16)
    bucket = 768
    fn = eng._make_paged_prefill(bucket)
    ints = eng._prefill_ints(np.arange(bucket - 5) % 60, bucket, 0,
                             eng.cache_manager.lane_tables(0))
    text = fn.trace(eng.params, eng.cache_manager.cache, jnp.asarray(ints),
                    jnp.ones(2, jnp.float32), jax.random.PRNGKey(0)).lower(
        lowering_platforms=("tpu",)).as_text()
    counts = _scatter_index_counts(text)
    # (the keys' and the values'; the layer's ``cache_index`` is a third)
    assert counts.count(bucket // 16 + 1) == 2 and max(counts) < bucket, counts
    # ... and the reader reads what it is meant to: the row form's text
    pools, new = _operands(1, "bfloat16", bucket)
    rows = jax.jit(lambda *a: row_form(*a, 1024)).lower(
        pools, new, jnp.zeros((1, 64), jnp.int32),
        jnp.zeros((1,), jnp.int32)).as_text()
    assert _scatter_index_counts(rows) == [bucket, bucket]
