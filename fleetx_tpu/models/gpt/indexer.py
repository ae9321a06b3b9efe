"""The learned indexer of sparse attention (DeepSeek's published one), as
BOTH attentions that run under it call it: latent attention
(``models/gpt/latent.py``: the queries from the query latent, the selection
feeding the absorbed kernel over a headless latent pool) and grouped
attention (``models/gpt/hybrid.py``: the queries from the layer's normed
input, the selection feeding ``fleetx_decode_paged`` and
``fleetx_gqa_sparse_prefill`` over a pool with heads). ONE copy.

A query at position ``t`` scores every row ``s <= t`` of its lane from ONE
index key a row (the pool's third leaf, ``cached_index``): ``I[t, s] =
sum_j w[t, j] ReLU(qI[t, j] . kI[s])``, float32 (:func:`index_scores`), and
keeps the ``min(index_topk, t + 1)`` highest, a tie to the lower row:

- a chunk scores its lane's index keys in blocks of ``KEY_BLOCK`` rows up
  to its last row (:func:`_chunk_index_scores`, scope ``dsa_index``) and
  selects EXACTLY by threshold, a mask ``[s, t]`` with each row's own set
  (:func:`select_rows`, scope ``dsa_select``);
- a tick selects by the SAME search over each lane's one row of scores and
  lays the chosen positions out in position order without a sort, counting
  them in blocks of 128 (:func:`top_rows`, ``dsa_select``), then gathers
  the chosen rows of the pool's other leaves into a compact pool of
  ``index_topk`` rows a lane (:func:`gather_rows`, ``dsa_attn``), which
  the caller's paged decode kernel attends over as it stands.

What a caller plants a fault in (``perfbench/probe_dsv32.py`` sets them on
``latent``, whose names these are by import) is looked up in ``seams``, the
caller's module where it passes one.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

__all__ = ["KEY_BLOCK", "gather_rows", "index_scores", "select_rows",
           "sow_selection", "top_rows"]

# key rows of one block of a chunk's scores in plain XLA
KEY_BLOCK = 1024
# positions of one block of a tick's dense layout (a vector's lanes)
_BLOCK = 128
_INDEX_TYPE = jnp.float32     # what an index score's products accumulate in
_HERE = sys.modules[__name__]


def _index_act(dots):
    """What a head's product passes before the heads are summed: ReLU."""
    return jax.nn.relu(dots)


def _index_head_weights(w):
    """The heads' weights ``w_{t,j}`` as the sum takes them."""
    return w


def _visible(seen):
    """The rows ``[s, t]`` bool a query may SELECT from: those it sees."""
    return seen


def index_scores(qi, w, ki, seams=None):
    """``I = sum_j w_j ReLU(qI_j . kI)``, float32: ``qi`` ``[..., s, heads,
    d]``, ``w`` ``[..., s, heads]`` float32, ``ki`` ``[..., t, d]``; ``[...,
    s, t]``."""
    seams = seams or _HERE
    dots = jnp.einsum("...shd,...td->...sht", qi, ki,
                      preferred_element_type=seams._INDEX_TYPE
                      ).astype(jnp.float32)
    return (seams._index_act(dots) * w[..., None]).sum(-2)


def _search_keys(scores, valid, k: int):
    """``scores`` ``[..., t]`` float32 as keys that order as unsigned
    integers (0, under every finite score's, where a row is not ``valid``)
    and how many rows each search is to take (``[..., 1]``)."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores), jnp.int32)   # (-0.0 is 0.0)
    key = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits), jnp.uint32
    ) ^ jnp.uint32(0x80000000)
    key = jnp.where(valid, key, 0)          # (a finite score's key is > 0)
    return key, jnp.minimum(valid.sum(-1, keepdims=True), k)


def _kth(key, want):
    """The ``want``-th largest of ``key`` ``[..., t]`` uint32, a bit at a
    time: 32 counts over the keys (``[..., 1]``; all ones where ``want`` is
    0)."""
    def bit(i, kth):
        higher = kth | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = (key >= higher).sum(-1, keepdims=True) >= want
        return jnp.where(enough, higher, kth)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros(key.shape[:-1] + (1,), jnp.uint32))


def select_rows(scores, valid, k: int):
    """The ``k`` highest of ``scores`` ``[..., t]`` float32 among the rows
    ``valid`` (all of them where there are no more than ``k``), a tie going
    to the lower position: ``[..., t]`` bool. EXACT, and no sort: the
    scores' bits, made to order as unsigned integers, are searched for the
    ``k``-th largest a bit at a time (32 counts over the scores), and the
    rows that tie with it are taken in order of position."""
    key, want = _search_keys(scores, valid, k)
    kth = _kth(key, want)
    above, ties = key > kth, (key == kth) & valid
    room = want - above.sum(-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= room))


def _chunk_index_scores(qi, w, ki, start, seams=None):
    """One lane's chunk ``qi`` ``[s, heads, d]`` at positions ``start + [0,
    s)`` against the lane's index keys ``ki`` ``[t, d]``, in blocks of
    ``KEY_BLOCK`` keys up to the chunk's last row (the rest stay 0: no
    query sees them): ``[s, t]`` float32."""
    s, t = qi.shape[0], ki.shape[0]
    block = min(KEY_BLOCK, t)
    if t % block:
        raise ValueError(f"a lane's {t} rows are no whole number of "
                         f"{block}-row key blocks")

    def one(i, out):
        part = index_scores(
            qi, w, jax.lax.dynamic_slice_in_dim(ki, i * block, block), seams)
        return jax.lax.dynamic_update_slice(out, part, (0, i * block))

    return jax.lax.fori_loop(
        0, jnp.minimum((start + s + block - 1) // block, t // block), one,
        jnp.zeros((s, t), jnp.float32))


def _counted(bits):
    """``bits`` ``[b, t]`` bool (``t`` whole blocks of ``_BLOCK``) counted
    in two levels: how many are set in a row's block up to and with the row
    (``[b, blocks, _BLOCK]`` int32: a triangular product on the MXU, exact,
    0/1 in bfloat16 summed in float32) and how many in the blocks before
    its block (``[b, blocks]`` int32: one masked sum ``[blocks, blocks]`` a
    lane, where ``jnp.cumsum`` is a dozen small programs)."""
    lanes = bits.shape[0]
    upto = jnp.arange(_BLOCK)[:, None] <= jnp.arange(_BLOCK)[None, :]
    rank = jnp.einsum("bnj,ji->bni",
                      bits.reshape(lanes, -1, _BLOCK).astype(jnp.bfloat16),
                      upto.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32).astype(jnp.int32)
    blocks = jnp.arange(rank.shape[1])
    return rank, jnp.where(blocks[None, :] < blocks[:, None],
                           rank[:, None, :, -1], 0).sum(-1)


def top_rows(scores, selectable, end, k: int):
    """A tick's selection: of every lane's ``scores`` ``[b, t]`` the ``k``
    best positions among ``selectable`` ``[b, t]`` bool, IN POSITION ORDER
    ``[b, k]`` int32, and how many of them name a row (``min(end, k)``
    ``[b]``: the places past that hold ``t``). A tie goes to the lower
    position. EXACT, and no sort: the rows are the ones :func:`select_rows`
    takes, by the same search, and they are laid out densely in two levels,
    blocks of ``_BLOCK`` positions (:func:`_counted`): each of the ``k``
    places finds the block that holds it (a comparison ``[k, blocks]``),
    fetches that block's running counts (a one-hot product ``[k, blocks] x
    [blocks, _BLOCK]`` on the MXU) and counts the rows before its own."""
    lanes, t = scores.shape
    pad = ((0, 0), (0, -t % _BLOCK))
    valid = jnp.pad(selectable, pad)
    key, want = _search_keys(jnp.pad(scores, pad), valid, k)
    kth = _kth(key, want)
    above, ties = key > kth, (key == kth) & valid
    room = want - above.sum(-1, keepdims=True)
    # (select_rows' last line, its sum along the lane in two levels)
    rank, before = _counted(ties)
    rank, before = _counted(above | (ties & (
        (rank + before[..., None]).reshape(lanes, -1) <= room)))
    place = jnp.arange(k, dtype=jnp.int32)
    behind = ((before + rank[..., -1])[:, None, :]
              <= place[None, :, None])                # [lanes, k, blocks]
    block = behind.sum(-1, dtype=jnp.int32)
    first = jnp.where(behind, rank[:, None, :, -1], 0).sum(-1)
    held = block[..., None] == jnp.arange(rank.shape[1], dtype=jnp.int32)
    # (a count is at most 128: exact in bfloat16)
    counts = jnp.einsum("bkn,bni->bki", held.astype(jnp.bfloat16),
                        rank.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    inside = (counts <= (place[None, :] - first)[..., None]).sum(
        -1, dtype=jnp.int32)
    count = jnp.minimum(end, k)
    return jnp.where(place[None, :] < count[:, None],
                     block * _BLOCK + inside, t), count


def gather_rows(pools, tables, chosen):
    """The rows ``chosen`` ``[b, k]`` (positions of each lane, ``t`` where a
    place names none) of every pool of ``pools`` (``[pages, page_size,
    width]`` under ``tables`` ``[b, pages of a lane]``, the layer's base
    added), gathered into compact pools of whole pages, ``k`` rounded up:
    ``(compact pools, their tables [b, pages])``."""
    b, k = chosen.shape
    ps = pools[0].shape[1]
    t = tables.shape[1] * ps
    kp = -(-k // ps) * ps           # whole pages of the compact pool
    at = jnp.minimum(jnp.pad(chosen, ((0, 0), (0, kp - k)),
                             constant_values=t), t - 1)
    row = jnp.take_along_axis(tables, at // ps, axis=1) * ps + at % ps
    compact = [pool.reshape(-1, pool.shape[-1])[row].reshape(
        b * kp // ps, ps, pool.shape[-1]) for pool in pools]
    return compact, jnp.arange(b * kp // ps, dtype=jnp.int32).reshape(b, -1)


def chosen_mask(chosen, t: int):
    """``chosen`` ``[b, k]`` positions as a mask ``[b, 1, t]`` bool (a
    place that names no row holds ``t`` and marks none)."""
    b = chosen.shape[0]
    return jnp.zeros((b, t + 1), bool).at[
        jnp.arange(b)[:, None], chosen].set(True)[:, None, :-1]


def sow_selection(module, index, chosen):
    """For whoever holds the indexer to a reference (the collection
    ``routing``, where it is mutable): the rows each query attends over
    ``[b, s, t]`` bool and, from a tick and from a forward outside the
    cache, the index scores, float32 alike."""
    if module.is_mutable_collection("routing"):
        module.sow("routing", "index_sets", chosen)
        if index is not None:
            module.sow("routing", "index_scores", index)
