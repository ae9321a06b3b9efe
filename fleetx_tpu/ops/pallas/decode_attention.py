"""Flash-decode: Pallas single-query attention over the kv cache.

The serving-side sibling of ops/pallas/flash_attention.py. During kv-cache
generation every step is one query token attending to the cache prefix
written so far — but the dense fallback streams the ENTIRE
``decode_cache_len`` buffer through HBM per step per layer, so a 1024-slot
cache costs 4x the traffic of a 256-token decode span. Decode attention is
purely bandwidth-bound (one [1, d] query does ~2*d FLOPs per cached key),
so HBM bytes touched IS the latency; this kernel makes those bytes scale
with the live prefix instead of the cache capacity.

Same idioms as the training kernel: online softmax (never materializes the
[1, cache_len] score row in HBM), major-block K/V streaming with an
in-kernel ``fori_loop`` over compute tiles, env-tunable block sizes, and
``interpret=True`` off-TPU so CPU tests execute the real kernel math.

Layout: the cache is LANE-DENSE. K/V buffers are ``[b, cache_len, h*d]``
(paged: ``[num_pages, page_size, h*d]``), one row of h*d lanes per cached
token with head g in lanes ``[g*d, (g+1)*d)``; int8 scales are
``[..., len, h]``. The TPU lowering only accepts blocks whose last two
dimensions are (8k, 128k) multiples or the array's own, so a block cannot
squeeze a heads axis that sits second-minor (the seed's
``[b, len, h, d]`` blocks never compiled), and a 64-wide head_dim minor
axis pads every HBM tile to 128 lanes. With heads folded into the lane
axis a block is ``[rows, h*d]``: legal for every page size that is a
multiple of 8, dense in HBM, and it needs no per-step repack because the
cache is STORED this way (models/gpt/model.py writes it). One grid step
serves all heads of a batch row (grid ``(b, blocks)``); the body turns the
query into a block-diagonal ``[h, h*d]`` matrix so two MXU matmuls per
tile do every head's dot products (:func:`_decode_kernel`).

What's different from the training kernel:
- q_len == 1: no causal structure inside a step. The valid key window per
  batch row is the contiguous ``[starts[b], end)`` — ``starts`` are the
  left-pad counts of the prompt (pads sit at the FRONT of the cache; see
  generation.py kv layout) and ``end`` is ``cache_index`` after this
  step's write (the query's own position + 1).
- ``end``/``starts`` are TRACED values (the loop counter of the decode
  ``while_loop``), so the dead-block skip cannot be a Python-level grid
  trim. They are fed through ``pltpu.PrefetchScalarGridSpec`` scalar
  prefetch: the K/V index maps clamp the streamed block index into the
  live ``[first, last]`` major-block range, so grid steps outside it
  repeat a resident index (NO HBM DMA) and ``pl.when`` retires them
  without compute. Per-step traffic is ceil(end/major) blocks — the
  tokens decoded so far — not ``cache_len``.
- forward-only: decode never differentiates, so there is no VJP, no lse
  output, and no dropout plumbing.

Empty windows: a batch row with ``end <= starts`` attends over nothing. The
serving engine's tick carries such rows, the lanes that decode no token
(the models hand them ``end = 0``: ``models/gpt/paged_write.decode_end``),
and they cost the kernels nothing: no step runs for the row and no copy is
started for it, its K/V index map repeats one block (never the table at
-1), and its output block is written once, at the row's step 0, as exact
zeros. The paged kernel's chain of copies passes over such rows.

Grouped-query heads: the cache may hold FEWER heads than the query has
(``kv_heads * d`` lanes a row; query head r reads key head ``r // group``).
The kernels are the same walk with the block-diagonal query built for the
cache's lanes: row r holds query head r's d values on the lanes of key head
``r // group``, so one product against the K tile still gives every query
head's scores, and the product with the V tile ``[rows, kv_heads * d]``
whose block ``(r, r // group)`` is head r's output. With as many key heads
as query heads (group 1) the query arrives as one lane-dense row and the
kernel spreads it, as it always did; with fewer, the wrapper lays the
``[rows, kv_heads * d]`` matrix out (28 x 512 at most: nothing beside the
cache rows) and picks each head's block of the result, so the kernel
neither tiles nor slices lanes. Int8 scales and a mesh are refused there.

Int8 KV (``k_scale``/``v_scale`` given): K/V stream from HBM as int8 with
one fp32 scale per cached (row, head) vector (``ops/quant.quantize_kv``
values, scales stored ``[..., cache_len, h]``). A (row, head) scale is
constant across that head's d lanes, so it factors out of both dot
products: the int8 tile goes to the MXU as exact small integers and the
``[h, block_k]`` score / probability tile is multiplied by the scale tile
— the HBM bytes per decode step roughly halve while the result equals
dequantizing up front to within f32 rounding (and is closer to the exact
value than a bf16 dequantized operand). The dense/XLA fallback uses the
``dequantize_kv`` helper, keeping every path on one quantization contract
(docs/QUANTIZATION.md).

Mesh-sharded decode (``mesh=`` on both entry points): under a TP/FSDP
serving mesh the KV cache lives head-sharded on ``mp``
(serving/engine.py "Mesh-sharded serving": the lane axis splits into
whole-head groups), and a bare Pallas call over sharded operands would
make GSPMD replicate them — an all-gather of the whole pool per step,
defeating the kernel. Instead the call is wrapped in ``shard_map`` over
the local head slice: per-head online softmax is independent across
heads, so each device streams only ITS heads' live prefix (the
HBM-traffic contract holds per device) and the result is bit-identical to
the unsharded kernel. ``starts``/``ends`` and the paged block tables are
replicated; the logits all-gather happens only at the row-parallel output
projection GSPMD already manages.

Paged variant (:func:`flash_decode_paged_attention`): the serving engine's
page-granular cache stores K/V as ``[num_pages, page_size, h*d]`` shared
pages and each batch row addresses its logical window through a block
table of page indices (serving/cache_manager.py), which rides scalar
prefetch next to ``starts``/``ends``. The body is THE SAME online-softmax
walk (:func:`_decode_kernel`); what differs is how a step's rows reach
VMEM, and how many they are. The grid follows what a lane can have live
(:func:`paged_grid`), in both its axes:
- **rows a step, from the row's width.** A grid step covers **P consecutive
  logical pages** of a row, one compute tile: ``block_k`` rows
  (``FLEETX_DECODE_BLOCK_K``, 256: the contiguous kernel's tile) where a
  row is 2,048 bfloat16 lanes or more, and as many more rows of a narrower
  row as hold the same 1 MB (:func:`_pages_per_step`), capped at the
  table's width: 16 pages of 16 rows where a row is 16 heads of 128, 32
  where it is 8, 64 where it is 4 heads of 128 or 8 of 64. The MXU pays a
  matmul by the weight tiles it loads, not by the rows of the tile, so a
  step of one 16-row page cost what a step of 256 rows costs (PERF.md,
  PR 30); and a step pays its launch, its products' set-up and its copies'
  starts beside its bytes, so a narrow row's step of 256 rows ran at 40% of
  its bytes' pace where the full row's runs at 70-85% (PERF.md, PR 50).
- **steps a lane, from a bound on its window.** Step jm of a lane is
  logical block ``starts // rows + jm``: a lane's steps begin in the block
  its window does, and the grid is ``(b, steps)`` with ``steps`` what
  ``max_live`` rows can touch (one more than the blocks they fill), never
  more than ``ceil(pages of a row / P)``, which is the grid of a call that
  gives no bound. A window layer (models/gpt/hybrid.py) hands its window
  over, so its grid is as long as the window and not as the table.
- P > 1 (:func:`_paged_block_call`): the pools stay in HBM and a live
  step's LIVE pages are copied, one async copy a page through the row's
  table, side by side into a double-buffered ``[2, P * page_size, h*d]``
  VMEM tile; each live step starts the next live step's copies (the next
  lane's that HAS a window) before it waits for its own. Pages of the
  block outside ``[first, last]`` are not copied and their rows are masked
  by position, so a call reads the rows' live pages, rounded up to pages
  and never to blocks; a step wholly outside the window does nothing.
- P = 1 (a page is over half of ``block_k`` rows already, or a page is
  below the pool dtype's packed tile, as a 16-row int8 page is half a
  (32, 128) tile and P of them would need a relayout to lie side by side):
  a step is a page, every page of the table a step whatever the bound,
  streamed by the BlockSpec pipeline like the contiguous kernel's blocks
  with ``major == page_size``; the index map gathers physical page
  ``table[b, jm]``, and dead steps clamp into the live ``[first, last]``
  logical range, so they repeat a resident physical page (no DMA).
Either way pages shared between rows (prefix reuse) are simply gathered
by several rows' tables.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu.ops.pallas.flash_attention import (
    _NN,
    _NT,
    NEG_INF,
    _dot,
    _env_block,
    _interpret,
    _mm_dtype,
    kernels_enabled,
)

__all__ = [
    "CONTIGUOUS_KERNEL_NAME",
    "PAGED_KERNEL_NAME",
    "flash_decode_attention",
    "flash_decode_paged_attention",
    "decode_flash_supported",
    "decode_mesh_shardable",
    "fit_decode_blocks",
    "paged_gather_kv",
    "paged_grid",
]

# Stable kernel names: they appear in the compiled HLO's custom-call
# instruction names (chip_smoke.py asserts the decode tick holds the paged
# one) and in profiler traces.
CONTIGUOUS_KERNEL_NAME = "fleetx_decode"
PAGED_KERNEL_NAME = "fleetx_decode_paged"

# Cache-dim tile sizes, swept independently of the training kernel's
# (decode tiles trade MXU shape for DMA granularity — the query side is one
# row, so there is no q-block dimension to balance against).
DEFAULT_DECODE_BLOCK_K = _env_block("FLEETX_DECODE_BLOCK_K", 256)
# rows of K and V resident in VMEM per grid step (the HBM->VMEM DMA unit)
DEFAULT_DECODE_BLOCK_MAJOR = _env_block("FLEETX_DECODE_BLOCK_MAJOR", 1024)


def fit_decode_blocks(cache_len: int,
                      want_k: Optional[int] = None,
                      want_major: Optional[int] = None):
    """(block_k, major) tiling ``cache_len``, or (None, None) if no 8-row
    tile divides it. Largest divisor <= the requested sizes, mirroring
    flash_attention.fit_blocks. Trace-time Python only. Eight rows is the
    rule for every cache dtype: Mosaic pads a block below the packed
    tile (16 rows bf16, 32 int8) itself, so a 16-row int8 page compiles
    (tests/test_decode_attention.py cross-lowers each combination)."""
    want_k = DEFAULT_DECODE_BLOCK_K if want_k is None else want_k
    want_major = DEFAULT_DECODE_BLOCK_MAJOR if want_major is None else want_major
    want_k = min(want_k, cache_len)
    block_k = next(
        (bk for bk in range(want_k - want_k % 8, 7, -8)
         if cache_len % bk == 0), None
    )
    if block_k is None:
        return None, None
    n = cache_len // block_k
    t = min(n, max(want_major // block_k, 1))
    while n % t:
        t -= 1
    return block_k, t * block_k


def decode_flash_supported(cache_len: int) -> bool:
    """Static dispatch check for the model layer: the cache (or page)
    length tiles, and we are on a TPU (or the interpreter is explicitly
    forced — the CPU decode parity tests set FLEETX_FORCE_FLASH=1)."""
    block_k, _ = fit_decode_blocks(cache_len)
    return block_k is not None and kernels_enabled()


def _data_extent(mesh) -> int:
    """dp*fsdp world of a mesh — the axes one-shot callers batch-shard
    activations (and decode caches) over."""
    sizes = dict(mesh.shape)
    return sizes.get("dp", 1) * sizes.get("fsdp", 1)


def decode_mesh_shardable(mesh, num_heads: int,
                          batch: Optional[int] = None) -> bool:
    """True when the decode kernels can run per-shard under ``mesh``
    (module docstring "Mesh-sharded decode"): no pp/cp extents (the
    shard_map's specs would treat those axes as replicated, all-gathering
    pipeline-stage or cp-sharded operands around the kernel), the
    attention heads must divide over the ``mp`` extent, and — when the
    mesh has dp/fsdp extents and the caller supplied ``batch`` — the
    batch must divide over them too. One-shot ``generate()`` under a
    data-parallel mesh keeps its cache batch-sharded over (dp, fsdp); a
    shard_map that replicated that axis would all-gather the whole cache
    per step (the exact pathology the old dense fallback avoided), so a
    non-dividing batch keeps the dense path. The per-head/per-row
    online-softmax walk is embarrassingly parallel, so a sliced kernel
    call is bit-identical to the unsharded one."""
    sizes = dict(mesh.shape)
    if sizes.get("pp", 1) > 1 or sizes.get("cp", 1) > 1:
        return False
    if num_heads % sizes.get("mp", 1):
        return False
    n_data = _data_extent(mesh)
    return n_data == 1 or batch is None or batch % n_data == 0


def _decode_specs(mesh, batch: Optional[int]):
    """(batch axes, q/out spec, cache spec) for the decode shard_map:
    heads on mp — axis 2 of the rank-4 q/out [b, 1, h, d], and the lane
    axis of the rank-3 caches (K/V [.., len, h*d] and scales [.., len, h]
    both split into whole-head groups) — batch over (dp, fsdp) when
    ``batch`` is given and divides. Sharding a replicated operand
    merely slices it; the guard in :func:`decode_mesh_shardable` keeps
    the reverse (replicating a batch-sharded cache = a per-step
    all-gather) off this path. ``batch=None`` = never shard axis 0
    (the paged pools' page axis is shared by every row)."""
    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh.shape)
    head = "mp" if sizes.get("mp", 1) > 1 else None
    data = tuple(a for a in ("dp", "fsdp") if sizes.get(a, 1) > 1)
    if batch is None or not batch or (data and batch % _data_extent(mesh)):
        data = ()  # direct callers without the guard: replicate batch
    batch_axes = data or None
    return (batch_axes, P(batch_axes, None, head, None),
            P(batch_axes, None, head))


def _sharded_decode(mesh, starts_b, ends_b, operands, tables=None,
                    block_k=None, block_major=None, max_live=None):
    """shard_map both decode kernels over (heads -> mp; contiguous
    batch -> dp/fsdp when it divides). Without this, GSPMD treats the
    Pallas call as an opaque custom call and REPLICATES the sharded
    q/cache operands — an all-gather of the whole KV pool around the
    one kernel whose purpose is to bound HBM traffic (the PR 1
    "meshes -> dense XLA fallback" guard existed exactly because of
    that). The manual region hands each device its local slice;
    ``starts``/``ends`` follow the batch axes, and the per-row/per-head
    math is the unsharded kernel's bit-for-bit, so mesh serving keeps
    byte parity.

    ``operands`` is [q, k, v] (+ [k_scale, v_scale] at int8); ``tables``
    flips the paged variant on. Scale operands share the K/V lane axis
    (h heads beside h*d lanes), so one spec serves all four. Batch
    layouts differ:
    the CONTIGUOUS buffers carry batch at axis 0, matching one-shot
    ``generate()``'s dp/fsdp-sharded cache (:func:`decode_mesh_shardable`
    keeps non-dividing batches off this path); the PAGED pools carry
    PAGES at axis 0 — shared by every row's table — so the paged
    variant (serving-only, batch replicated by design) never shards it."""
    from jax.sharding import PartitionSpec as P

    from fleetx_tpu.parallel.mesh import shard_map

    if tables is None:
        batch_axes, q_spec, kv_spec = _decode_specs(
            mesh, operands[0].shape[0])

        def body(starts, ends, q, k, v, *scales):
            ks, vs = scales if scales else (None, None)
            return flash_decode_attention(
                q, k, v, end=ends, starts=starts, block_k=block_k,
                block_major=block_major, k_scale=ks, v_scale=vs)

        in_specs = ((P(batch_axes), P(batch_axes), q_spec)
                    + (kv_spec,) * (len(operands) - 1))
        fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=q_spec,
                       check_vma=False)
        return fn(starts_b, ends_b, *operands)

    # heads-only: the pool axis stays whole
    _, q_spec, kv_spec = _decode_specs(mesh, None)

    def pbody(starts, ends, tables, q, k, v, *scales):
        ks, vs = scales if scales else (None, None)
        return flash_decode_paged_attention(
            q, k, v, tables=tables, end=ends, starts=starts,
            block_k=block_k, k_scale=ks, v_scale=vs, max_live=max_live)

    in_specs = ((P(None), P(None), P(None, None), q_spec)
                + (kv_spec,) * (len(operands) - 1))
    fn = shard_map(pbody, mesh=mesh, in_specs=in_specs, out_specs=q_spec,
                   check_vma=False)
    return fn(starts_b, ends_b, tables, *operands)


def _decode_kernel(starts_ref, ends_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, block_k: int, major: int,
                   scale: float, heads: int, ks_ref=None, vs_ref=None,
                   gather=None, group: int = 1):
    """Grid step (batch bi, step jm): online-softmax update of ALL heads'
    single query row against the live tiles of the resident major block.
    Step jm is logical block ``blk`` = jm of the row, or, under ``gather``,
    block ``start // major + jm``: counted from the block the row's window
    starts in, so that a row's live steps are its first ones.

    Refs are lane-dense (module docstring "Layout"): ``q_ref``/``o_ref``
    [1, h*d], ``k_ref``/``v_ref`` [major, h*d]. The query becomes a
    block-diagonal [rows, h*d] matrix (row r holds head r's d lanes,
    zeros elsewhere), so ONE transposed-rhs matmul against the K tile
    yields every head's scores [rows, block_k] and one matmul of the
    probabilities against the V tile yields [rows, h*d], whose diagonal
    blocks are the per-head outputs. The zero lanes contribute exact
    zeros, so per-head math is the plain dot product; the off-diagonal
    products are MXU slack on a bandwidth-bound kernel.

    Every tile intersecting ``[start, end)`` runs masked — with one query
    row per head the mask is a [1, block_k] compare, noise next to the
    two dots, so the training kernel's free/masked two-phase walk buys
    nothing here.

    ``ks_ref``/``vs_ref`` (int8 KV mode) are the per-vector fp32 scale
    blocks [major, h] riding the same index map as K/V. A (key, head)
    scale is constant over that head's d lanes, so it factors out of both
    dots: the int8 tile enters the MXU as exact small integers and the
    [rows, block_k] score / probability tile is multiplied by the
    transposed scale tile (module docstring "Int8 KV").

    ``gather`` (the paged kernel at several pages a step,
    :func:`_paged_block_call`) is called with ``blk`` at the top of a live
    step and returns the cache refs in place of the four above: the block's
    pages copied side by side into VMEM.

    ``group`` > 1 (module docstring "Grouped-query heads"): the cache holds
    ``heads // group`` heads, ``q_ref`` is the block-diagonal ``[rows,
    kv_heads * d]`` query already and ``o_ref`` takes the accumulator's
    diagonal blocks in place, ``[rows, kv_heads * d]``."""
    bi = pl.program_id(0)
    jm = pl.program_id(1)
    start = starts_ref[bi]
    end = ends_ref[bi]
    # an empty window (a lane that decodes no token, module docstring
    # "Empty windows") is the lane's step 0 and nothing else: the state is
    # zeroed and finalized there, so its output block is exact zeros
    empty = end <= start
    base = 0 if gather is None else start // major
    blk = base + jm
    first = jnp.where(empty, base, start // major)
    # never past the grid's last step, whatever ``end`` says
    last = jnp.where(empty, base, jnp.minimum(
        (end - 1) // major, base + pl.num_programs(1) - 1))
    tiles = major // block_k
    rows, hd = acc_scr.shape
    d = hd // (heads // group)
    # diag[r, c] <=> lane c belongs to (the key head of) head r (rows past
    # ``heads`` are sublane padding: never selected, their state stays inert)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 0)
    diag = ((row if group == 1 else row // group)
            == jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 1) // d)

    @pl.when(blk == first)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(~empty & (blk >= first) & (blk <= last))
    def _step():
        k_rows, v_rows, ks_rows, vs_rows = (
            (k_ref, v_ref, ks_ref, vs_ref) if gather is None
            else gather(blk))
        mm_dt = _mm_dtype(q_ref.dtype)
        # select in f32: the iota mask has the 32-bit tile layout, which
        # Mosaic will not relayout onto a packed bf16 operand
        q_bd = (jnp.where(diag, q_ref[:].astype(jnp.float32), 0.0
                          ).astype(mm_dt) if group == 1
                else q_ref[:].astype(mm_dt))
        # local tile range intersecting the valid window [start, end)
        t_lo = jnp.clip((start - blk * major) // block_k, 0, tiles)
        t_hi = jnp.clip(
            (end - blk * major + block_k - 1) // block_k, 0, tiles
        )

        def body(t, carry):
            m, l, acc = carry
            row0 = pl.multiple_of(t * block_k, block_k)
            k_blk = k_rows[pl.ds(row0, block_k), :].astype(mm_dt)
            v_blk = v_rows[pl.ds(row0, block_k), :].astype(mm_dt)
            s = _dot(q_bd, k_blk, _NT) * scale  # [rows, block_k]
            if ks_rows is not None:
                s = s * _scale_rows(ks_rows[pl.ds(row0, block_k), :], rows)
            k_row = (blk * major + t * block_k
                     + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
            s = jnp.where((k_row >= start) & (k_row < end), s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            # keep p exactly 0 on masked lanes so poisoned/unwritten cache
            # slots inside a boundary tile cannot leak through p @ v
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            if vs_rows is not None:
                p = p * _scale_rows(vs_rows[pl.ds(row0, block_k), :], rows)
            acc_new = alpha * acc + _dot(p.astype(mm_dt), v_blk, _NN)
            return m_new, l_new, acc_new

        carry = (m_scr[:], l_scr[:], acc_scr[:])
        m, l, acc = jax.lax.fori_loop(t_lo, t_hi, body, carry)
        m_scr[:] = m
        l_scr[:] = l
        acc_scr[:] = acc

    @pl.when(blk == last)
    def _finalize():
        l = l_scr[:]
        # a live window holds the query's own position, so l > 0; an empty
        # one arrives with l and acc as _init left them, and the guard
        # makes its block 0 / 1
        l_safe = jnp.where(l > 0.0, l, 1.0)
        out = jnp.where(diag, acc_scr[:] / l_safe, 0.0)
        if group == 1:
            out = jnp.sum(out, axis=0, keepdims=True)
        o_ref[:] = out.astype(o_ref.dtype)


def _scale_rows(scale_tile, rows: int):
    """[block_k, h] per-vector scales -> [rows, block_k], the orientation
    of the score tile. An identity matmul at full f32 precision moves the
    heads axis from lanes to sublanes exactly (1.0 * x plus zeros); Mosaic
    has no transpose for tiles this far below (8, 128)."""
    h = scale_tile.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (rows, h), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (rows, h), 1))
    return jax.lax.dot_general(
        eye.astype(jnp.float32), scale_tile, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _kv_index_map(major: int):
    """K/V major-block index for grid step (bi, jm): clamped into the
    live [first, last] range of THIS batch row, so dead steps repeat a
    resident block index and trigger no DMA — the per-step HBM traffic is
    what scales with the decoded prefix."""

    def index_map(bi, jm, starts_ref, ends_ref):
        first = starts_ref[bi] // major
        # an empty window: every step repeats block ``first``
        last = jnp.maximum((ends_ref[bi] - 1) // major, first)
        return bi, jnp.clip(jm, first, last), 0

    return index_map


def _q_index_map(bi, jm, *_):
    return bi, 0, 0


def _sublane_rows(heads: int, dtype) -> int:
    """Rows of the block-diagonal query / accumulator: the head count
    rounded up to the matmul operand's sublane tile (16 bf16, 8 f32)."""
    tile = 16 if dtype == jnp.bfloat16 else 8
    return -(-heads // tile) * tile


def _query_operand(q, width: int):
    """``(operand, block shape, group)`` of the query ``[b, 1, h, d]``
    against a cache of ``width`` lanes a row: one lane-dense row where the
    cache holds every head (group 1), else the block-diagonal ``[b, rows,
    width]`` matrix (module docstring "Grouped-query heads")."""
    b, _, h, d = q.shape
    group = h * d // width
    if group == 1:
        return q.reshape(b, 1, h * d), (None, 1, h * d), 1
    kv_heads = h // group
    rows = _sublane_rows(h, _mm_dtype(q.dtype))
    mine = (jnp.arange(h)[:, None] // group
            == jnp.arange(kv_heads)[None, :]).astype(q.dtype)   # [h, kv]
    spread = q[:, 0, :, None, :] * mine[None, :, :, None]       # [b,h,kv,d]
    spread = jnp.pad(spread.reshape(b, h, width),
                     ((0, 0), (0, rows - h), (0, 0)))
    return spread, (None, rows, width), group


def _heads_of(out, q_shape, group: int):
    """The kernels' result back as ``[b, 1, h, d]``: with grouped heads,
    row r's block ``r // group`` of ``[b, rows, kv_heads * d]`` (the others
    are exact zeros, so a sum picks it)."""
    b, _, h, d = q_shape
    if group == 1:
        return out.reshape(b, 1, h, d)
    return out[:, :h].reshape(b, h, h // group, d).sum(axis=2)[:, None]


def _softmax_scratch(heads: int, width: int, q_dtype):
    """The online-softmax state one batch row carries along the block
    axis, as ``scratch_shapes`` entries: m, l and the accumulator."""
    rows = _sublane_rows(heads, _mm_dtype(q_dtype))
    return [
        pltpu.VMEM((rows, 1), jnp.float32),      # running max m
        pltpu.VMEM((rows, 1), jnp.float32),      # normalizer l
        pltpu.VMEM((rows, width), jnp.float32),  # accumulator
    ]


def _decode_call(name, q, cache_operands, index_map, rows_per_block: int,
                 prefetch, n_blocks: int, block_k: int):
    """The one ``pallas_call`` behind both entry points: grid (b, blocks),
    lane-dense q/out [b, 1, h*d], scratch carried along the block axis.

    ``cache_operands`` is [k, v] (+ [k_scale, v_scale] at int8): each is
    blocked ``rows_per_block`` cache rows by its full lane width through
    the SAME ``index_map`` — scales ride the K/V map, so a dead grid step
    repeats resident scale blocks exactly like resident K/V blocks (no
    DMA). ``prefetch`` is [starts, ends] (+ [tables] paged; the table is
    consumed by the index map only). ``name`` is the kernel's stable name
    in compiled HLO and in traces."""
    b, _, h, d = q.shape
    quant = len(cache_operands) == 4
    width = cache_operands[0].shape[-1]
    q_in, q_block, group = _query_operand(q, width)

    def kernel(*refs):
        # pallas_call's ref order: scalar prefetch, inputs, output, scratch
        starts_ref, ends_ref = refs[:2]
        q_ref, k_ref, v_ref, *rest = refs[len(prefetch):]
        ks_ref, vs_ref = rest[:2] if quant else (None, None)
        o_ref, m_scr, l_scr, acc_scr = rest[2 if quant else 0:]
        _decode_kernel(starts_ref, ends_ref, q_ref, k_ref, v_ref, o_ref,
                       m_scr, l_scr, acc_scr, block_k=block_k,
                       major=rows_per_block, scale=1.0 / (d**0.5), heads=h,
                       ks_ref=ks_ref, vs_ref=vs_ref, group=group)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, n_blocks),
        in_specs=[pl.BlockSpec(q_block, _q_index_map)] + [
            pl.BlockSpec((None, rows_per_block, x.shape[-1]), index_map)
            for x in cache_operands],
        out_specs=pl.BlockSpec(q_block, _q_index_map),
        scratch_shapes=_softmax_scratch(h, width, q.dtype),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_in.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the block axis carries the online-softmax scratch state
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_interpret(),
        name=name,
    )(*prefetch, q_in, *cache_operands)
    return _heads_of(out, q.shape, group)


def _cache_operands(k, v, k_scale, v_scale):
    return [k, v] + ([k_scale, v_scale] if k_scale is not None else [])


def _window(b: int, end, starts):
    """([b] starts, [b] ends) int32 from the entry points' loose forms."""
    ends_b = jnp.broadcast_to(jnp.asarray(end, jnp.int32), (b,))
    starts_b = (jnp.zeros((b,), jnp.int32) if starts is None
                else starts.astype(jnp.int32))
    return starts_b, ends_b


def _check_operands(q, k, k_scale, v_scale, meshed: bool = False):
    b, sq, h, d = q.shape
    if sq != 1:
        raise ValueError(f"flash decode is single-query (q_len={sq})")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("int8 KV needs BOTH k_scale and v_scale")
    if k.ndim != 3 or (h * d) % k.shape[-1] or k.shape[-1] % d:
        raise ValueError(
            f"flash decode reads the lane-dense cache [..., len, h*d="
            f"{h * d}] (or kv_heads*d lanes, kv_heads dividing h={h}); "
            f"got K {k.shape}")
    if k.shape[-1] != h * d and (k_scale is not None or meshed):
        raise NotImplementedError(
            "flash decode over grouped heads takes no int8 scales and no "
            "mesh (module docstring \"Grouped-query heads\")")


def flash_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    end: jax.Array,
    starts: Optional[jax.Array] = None,
    block_k: Optional[int] = None,
    block_major: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    mesh=None,
) -> jax.Array:
    """Single-query attention against the kv cache; q is [b, 1, h, d].

    ``k``/``v`` are the FULL lane-dense cache buffers [b, cache_len, h*d]
    (module docstring "Layout"); ``end`` (traced int32 scalar or [b]) is
    the number of live cache positions — ``cache_index`` after this
    step's write — and ``starts`` ([b] int32, optional) the per-row first
    valid position (left-pad count). Row b attends exactly the window
    [starts[b], end). No scaling/softmax state leaves the kernel; output
    is [b, 1, h, d] in ``q``'s dtype.

    ``k_scale``/``v_scale`` ([b, cache_len, h] fp32, given together)
    switch the kernel to int8-KV mode: ``k``/``v`` are int8 per
    ``ops/quant.quantize_kv`` (module docstring "Int8 KV").

    ``cache_len`` must be a multiple of 8 (checked; callers pre-screen with
    :func:`decode_flash_supported` and take the XLA path otherwise).

    ``mesh`` invokes the kernel per-shard inside ``shard_map`` over the
    local head slice (:func:`_sharded_decode`): heads split on ``mp``,
    scalars/tables replicated — callers pre-screen with
    :func:`decode_mesh_shardable`.
    """
    meshed = mesh is not None and mesh.size > 1
    _check_operands(q, k, k_scale, v_scale, meshed)
    starts_b, ends_b = _window(q.shape[0], end, starts)
    operands = _cache_operands(k, v, k_scale, v_scale)
    if meshed:
        return _sharded_decode(mesh, starts_b, ends_b, [q] + operands,
                               block_k=block_k, block_major=block_major)
    cache_len = k.shape[1]
    block_k, major = fit_decode_blocks(cache_len, block_k, block_major)
    if block_k is None:
        raise ValueError(
            f"cache_len {cache_len} not tileable (must be a multiple of 8)"
        )
    return _decode_call(CONTIGUOUS_KERNEL_NAME, q, operands,
                        _kv_index_map(major), major, [starts_b, ends_b],
                        cache_len // major, block_k)


# ------------------------------------------------------------- paged variant


def _paged_kv_index_map(page_size: int):
    """Physical-page index for grid step (bi, jm), where ``jm`` is row
    bi's LOGICAL page index: the block-table gather happens entirely
    here, so the online-softmax body is the contiguous kernel's with
    major=page_size (its ``k_row`` is the logical position jm*page_size +
    offset, which this map made physically resident). The row's table
    translates the logical page index into a physical page of the
    ``[num_pages, page_size, h*d]`` pool; jm is first clamped into the
    row's live logical range so dead steps re-address a resident page (no
    DMA), exactly like the contiguous kernel's clamp."""

    def index_map(bi, jm, starts_ref, ends_ref, tables_ref):
        first = starts_ref[bi] // page_size
        # an empty window: every step repeats page ``first``, and the
        # table is never read at -1
        last = jnp.maximum((ends_ref[bi] - 1) // page_size, first)
        return tables_ref[bi, jnp.clip(jm, first, last)], 0, 0

    return index_map


def _packed_rows(dtype) -> int:
    """Sublane rows of one packed VMEM tile of ``dtype``: 8 of 32 bits,
    16 of bfloat16, 32 of int8."""
    return 32 // jnp.dtype(dtype).itemsize


# The row the paged kernel's step was sized at: 16 heads of 128 in bfloat16
# (PERF.md, PR 30). A step of ``block_k`` such rows holds 1 MB of one cache
# operand in one half of its buffer, and a narrower row's step holds as many
# more rows as make the same bytes.
_FULL_ROW_BYTES = 2048 * 2
# pages whose copies are started unrolled (8: 1.7% slower, 32: level)
_START_UNROLL = 16


def _pages_per_step(page_size: int, n_pages: int, operands,
                    block_k: Optional[int]) -> int:
    """P, the pages one grid step of the paged kernel covers, which is one
    compute tile: ``block_k`` rows (default ``DEFAULT_DECODE_BLOCK_K``) of
    ``_FULL_ROW_BYTES`` or more, and as many more of a narrower row of the
    K pool as hold the same bytes (256 rows of 2,048 bfloat16 lanes, 512 of
    1,024, 1,024 of 512), over the page size, capped at the table's width.
    A step costs its launch, its products' set-up and its copies' starts
    whatever the bytes, so at 256 rows a row of 512 lanes ran at 37-41% of
    its bytes' pace where a row of 2,048 runs at 70-85% (PERF.md, PR 50).
    1 (a step is a page) where a page is over half of ``block_k`` already,
    or where a page of some cache operand is below that dtype's packed tile
    (an int8 page of 16 rows is half a (32, 128) tile), so P of them cannot
    lie side by side without a relayout."""
    want = DEFAULT_DECODE_BLOCK_K if block_k is None else block_k
    if want // page_size <= 1 or any(
            page_size % _packed_rows(x.dtype) for x in operands):
        return 1
    k = operands[0]
    row_bytes = k.shape[-1] * jnp.dtype(k.dtype).itemsize
    rows = want * max(1, _FULL_ROW_BYTES // row_bytes)
    return max(1, min(rows // page_size, n_pages))


def paged_grid(pools, n_pages: int, block_k: Optional[int] = None,
               max_live: Optional[int] = None):
    """``(P, steps)``: the pages one grid step of the paged kernel covers
    (:func:`_pages_per_step`) and the steps it walks a lane, for the cache
    operands ``pools`` (arrays or shapes ``[num_pages, page_size, width]``;
    K first), a table of ``n_pages`` pages a lane and ``max_live``, a static
    bound on the rows of a lane's window (None: the table's). The kernel
    sizes its grid by this function and the serving engine counts a tick's
    steps by it (``serving.decode``'s ``kernel_steps``).

    At P > 1 a lane's steps are consecutive blocks from the one its window
    starts in, so they number what ``max_live`` rows can touch: one more
    than the blocks they fill, and never more than the table has. At P = 1
    a step is a page of the table, every one."""
    page_size = pools[0].shape[1]
    pages = _pages_per_step(page_size, n_pages, pools, block_k)
    steps = -(-n_pages // pages)
    if pages > 1 and max_live is not None:
        steps = min(steps, -(-max_live // (pages * page_size)) + 1)
    return pages, steps


def _paged_block_call(q, pools, starts_b, ends_b, tables_b, pages: int,
                      steps: int):
    """The paged kernel at ``pages`` > 1 pages a grid step: grid
    ``(b, steps)`` (:func:`paged_grid`), and a step's tile is ``pages *
    page_size`` logical rows of one lane, contiguous in VMEM, so the two
    products run on ONE tile of that many rows as in the contiguous kernel
    (tiles of 256 rows inside a step of 1,024 took a third longer at 512
    lanes: PERF.md, PR 50).

    Step ``jm`` of a lane is logical block ``starts // rows + jm``: a
    lane's steps begin where its window does, so a window layer's grid is
    as long as the window and not as the table. Blocks behind ``end``, and
    behind the table, are dead steps.

    The pools stay in HBM (``pl.ANY``). A live step's pages are gathered
    through the lane's table by one async copy a LIVE page into its place
    in a ``[2, rows, width]`` buffer per cache operand: pages of the block
    outside the lane's ``[first, last]`` are not copied (their rows hold
    whatever an earlier step left, finite, and ``_decode_kernel`` masks
    them by position), so the bytes a call reads are the lanes' live
    pages, never rounded up to blocks. Each live step starts the NEXT live
    step's copies (this lane's next block, else the first block of the
    next lane that HAS a window, ``next_live``: the lanes between decode no
    token) into the other half before it waits for its own, so the gather
    runs under the step before it; a dead step does nothing. The waits are
    one for each power of two in the number of pages copied, not one a
    page. Both grid axes are sequential: the buffer half and whether it is
    already on its way pass from step to step in SMEM (``state``)."""
    b, _, h, d = q.shape
    ps = pools[0].shape[1]
    n_pages = tables_b.shape[1]
    rows = pages * ps
    n_ops = len(pools)
    width = pools[0].shape[-1]
    group_pages = min(pages, _START_UNROLL)
    q_in, q_block, group = _query_operand(q, width)

    # the chain passes over empty lanes: each lane's next lane WITH a
    # window (``b`` behind the last), so that a lane's last step starts that
    # lane's first copies however many free lanes lie between
    live = jnp.where(ends_b > starts_b, jnp.arange(b, dtype=jnp.int32), b)
    next_live = jnp.append(jax.lax.cummin(live, reverse=True)[1:],
                           jnp.int32(b))

    def kernel(starts_ref, ends_ref, tables_ref, next_ref, q_ref, *rest):
        pool_refs, rest = rest[:n_ops], rest[n_ops:]
        o_ref, m_scr, l_scr, acc_scr = rest[:4]
        bufs, (sem, state) = rest[4:4 + n_ops], rest[4 + n_ops:]
        bi = pl.program_id(0)
        jm = pl.program_id(1)

        @pl.when((bi == 0) & (jm == 0))
        def _reset():
            state[0] = 0  # buffer half of the next live step
            state[1] = 0  # 1: that step's copies are already on their way
            for buf in bufs:
                # a row no copy ever wrote must be finite: p is exactly 0
                # on it, and 0 * NaN would still poison p @ v
                buf[...] = jnp.zeros(buf.shape, buf.dtype)

        def copies(lane, blk, half, wait):
            """Start (or wait for) the copies of block ``blk`` of ``lane``:
            its pages inside the lane's window, each to its own rows."""
            first = starts_ref[lane] // ps
            # never past the table, whatever ``end`` says
            last = jnp.minimum((ends_ref[lane] - 1) // ps, n_pages - 1)
            lo = jnp.clip(first - blk * pages, 0, pages)
            hi = jnp.clip(last + 1 - blk * pages, 0, pages)

            if wait:
                # the semaphore counts bytes, whatever copies brought them:
                # one wait for each power of two in the number of pages on
                # their way, not one a page
                on_way = hi - lo
                for k in (1 << e for e in range(pages.bit_length())):
                    @pl.when((on_way & k) != 0)
                    def _wait():
                        for buf in bufs:
                            part = buf.at[half, pl.ds(0, k * ps), :]
                            pltpu.make_async_copy(part, part,
                                                  sem.at[half]).wait()
                return

            def start(i, carry):
                page = tables_ref[lane, blk * pages + i]
                row0 = pl.multiple_of(i * ps, ps)
                for pool, buf in zip(pool_refs, bufs):
                    pltpu.make_async_copy(
                        pool.at[page], buf.at[half, pl.ds(row0, ps), :],
                        sem.at[half]).start()
                return carry

            # a start is a table read and a descriptor an operand on the
            # scalar core, which a step pays for beside its products: whole
            # groups of pages go unrolled (a tenth off a call's time at 512
            # lanes: PERF.md, PR 50), the ragged ends of the window a page
            # at a time
            g_lo, g_hi = -(-lo // group_pages), hi // group_pages
            lead = jnp.minimum(g_lo * group_pages, hi)

            def start_group(g, carry):
                for j in range(group_pages):
                    start(g * group_pages + j, carry)
                return carry

            jax.lax.fori_loop(lo, lead, start, 0)
            jax.lax.fori_loop(g_lo, g_hi, start_group, 0)
            jax.lax.fori_loop(jnp.maximum(g_hi * group_pages, lead), hi,
                              start, 0)

        def gather(blk):
            half = state[0]

            @pl.when(state[1] == 0)
            def _own():
                copies(bi, blk, half, wait=False)

            # this lane's next block, where the window reaches it and the
            # grid has a step for it
            same = (blk < (ends_ref[bi] - 1) // rows) & (jm + 1 < steps)
            nxt = next_ref[bi]
            ahead = same | (nxt < b)
            lane = jnp.where(same, bi, jnp.minimum(nxt, b - 1))
            nblk = jnp.where(same, blk + 1, starts_ref[lane] // rows)

            @pl.when(ahead)
            def _next():
                copies(lane, nblk, 1 - half, wait=False)

            state[0] = 1 - half
            state[1] = ahead.astype(jnp.int32)
            copies(bi, blk, half, wait=True)
            views = [buf.at[half] for buf in bufs]
            return views if n_ops == 4 else views + [None, None]

        _decode_kernel(starts_ref, ends_ref, q_ref, None, None, o_ref,
                       m_scr, l_scr, acc_scr, block_k=rows, major=rows,
                       scale=1.0 / (d**0.5), heads=h, gather=gather,
                       group=group)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, steps),
        in_specs=[pl.BlockSpec(q_block, _q_index_map)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n_ops,
        out_specs=pl.BlockSpec(q_block, _q_index_map),
        scratch_shapes=_softmax_scratch(h, width, q.dtype)
        + [pltpu.VMEM((2, rows, x.shape[-1]), x.dtype) for x in pools]
        + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((2,), jnp.int32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_in.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the lane axis too: a lane's last step starts the next
            # lane's first copies
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=_interpret(),
        name=PAGED_KERNEL_NAME,
    )(starts_b, ends_b, tables_b, next_live, q_in, *pools)
    return _heads_of(out, q.shape, group)


def paged_gather_kv(pages: jax.Array, tables: jax.Array) -> jax.Array:
    """Dense-fallback gather: materialize each row's logical buffer
    ``[b, logical_len, width]`` from the shared page pool
    ``[num_pages, page_size, width]`` via its block table ``[b, n_pages]``
    (K/V pools: width h*d; scale pools: width h).

    The XLA parity path off-TPU (and for multi-token prefill, custom
    masks, meshes): it streams one logical cache's worth of HBM per call —
    the same traffic the contiguous dense fallback pays — so correctness
    fallbacks cost what they always cost, while the paged flash kernel
    above never materializes this buffer."""
    b, n_pages = tables.shape
    gathered = pages[tables]  # [b, n_pages, page_size, width]
    return gathered.reshape(b, n_pages * pages.shape[1], pages.shape[2])


def flash_decode_paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    *,
    tables: jax.Array,
    end: jax.Array,
    starts: Optional[jax.Array] = None,
    block_k: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    mesh=None,
    max_live: Optional[int] = None,
) -> jax.Array:
    """Single-query attention against a PAGED kv cache; q is [b, 1, h, d].

    ``k_pages``/``v_pages`` are the shared lane-dense page pools
    ``[num_pages, page_size, h*d]``; ``tables`` ([b, n_pages_per_row]
    int32) maps each row's logical page index to its physical page, and
    ``end`` ([b] or scalar int32, traced) is the row's live logical length
    (its window is ``[starts[b], end[b])`` in LOGICAL positions). Rows
    sharing prefix pages simply carry the same physical indices in their
    tables — the kernel reads shared pages like any other.

    ``k_scale``/``v_scale`` ([num_pages, page_size, h] fp32, given
    together) switch to int8-KV mode: the pools are int8 per
    ``ops/quant.quantize_kv`` and scale pages gather through the same
    block table (module docstring "Int8 KV").

    ``page_size`` must be a multiple of 8 (callers pre-screen with
    :func:`decode_flash_supported` on the page size). ``block_k`` is the
    rows of a compute tile of a row of 2,048 bfloat16 lanes, as in the
    contiguous kernel: a grid step gathers the pages of one tile, of as many
    more rows as a narrower row leaves room for (:func:`paged_grid`, module
    docstring "Paged variant"), and where a step stays one page ``block_k``
    tiles within it (largest divisor wins).
    ``max_live`` is a STATIC bound on ``end - starts`` that the caller
    vouches for (a window layer's window): the grid then walks a lane the
    steps that many rows can touch and not the table's; rows of a window
    beyond it would be left out.
    ``mesh`` runs the kernel per-shard over the local head slice of the
    page pools (tables replicated) — see :func:`flash_decode_attention`.
    """
    meshed = mesh is not None and mesh.size > 1
    _check_operands(q, k_pages, k_scale, v_scale, meshed)
    starts_b, ends_b = _window(q.shape[0], end, starts)
    tables_b = tables.astype(jnp.int32)
    operands = _cache_operands(k_pages, v_pages, k_scale, v_scale)
    if meshed:
        return _sharded_decode(mesh, starts_b, ends_b, [q] + operands,
                               tables=tables_b, block_k=block_k,
                               max_live=max_live)
    page_size = k_pages.shape[1]
    pages, steps = paged_grid(operands, tables.shape[1], block_k, max_live)
    if pages > 1:
        return _paged_block_call(q, operands, starts_b, ends_b, tables_b,
                                 pages, steps)
    # a step is one page (the gather unit); block_k tiles inside it
    block_k, major = fit_decode_blocks(page_size, block_k, page_size)
    if block_k is None or major != page_size:
        raise ValueError(
            f"page_size {page_size} not tileable (must be a multiple of 8)"
        )
    return _decode_call(PAGED_KERNEL_NAME, q, operands,
                        _paged_kv_index_map(page_size), page_size,
                        [starts_b, ends_b, tables_b], tables.shape[1],
                        block_k)
