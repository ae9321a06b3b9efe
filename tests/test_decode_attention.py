"""Flash-decode kernel + serving fast-path parity tests.

The Pallas single-query decode kernel (ops/pallas/decode_attention.py) runs
here in interpret mode (FLEETX_FORCE_FLASH=1 on the CPU test platform), so
the REAL kernel math — online softmax, live-window masking, scalar-prefetch
block clamping — is what gets checked, not a shadow implementation.

Parity contract (ISSUE 1): flash-decode and the dense XLA fallback must
produce byte-identical tokens for greedy and fixed-rng sampling, including
left-padded prompts and beam search."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fleetx_tpu.ops.pallas.decode_attention as da
from fleetx_tpu.models.gpt.generation import GenerationConfig, generate
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.ops.pallas.decode_attention import (
    PAGED_KERNEL_NAME,
    _pages_per_step,
    decode_flash_supported,
    fit_decode_blocks,
    flash_decode_attention,
    flash_decode_paged_attention,
    paged_grid,
)
from fleetx_tpu.ops.quant import dequantize_kv, quantize_kv

CFG = GPTConfig(
    vocab_size=97,
    hidden_size=48,
    num_layers=2,
    num_attention_heads=4,
    ffn_hidden_size=96,
    max_position_embeddings=64,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype=jnp.float32,
    use_flash_attention=True,
)

# f32 kernel-vs-dense tolerance, on the CPU interpreter and on the chip
# alike: Mosaic's f32 matmul is the exact product (the chip run read
# 5e-7), and the reference einsums below ask XLA for the same.
_TOL = 1e-5


@pytest.fixture(scope="module")
def model_and_params():
    model = GPTForPretraining(CFG)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return model, params


def _dense_window_attention(q, k, v, end, starts):
    """Reference: softmax over exactly the [starts[b], end[b]) key window,
    per-head [b, len, h, d] operands."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(d)
    pos = jnp.arange(k.shape[1])[None, None, None, :]
    end = jnp.broadcast_to(jnp.asarray(end), (q.shape[0],))
    valid = ((pos >= starts[:, None, None, None])
             & (pos < end[:, None, None, None]))
    p = jax.nn.softmax(jnp.where(valid, s, -1e9), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


def _fold(x):
    """[..., len, h, d] -> the lane-dense cache layout [..., len, h*d]."""
    return x.reshape(*x.shape[:-2], -1)


# ------------------------------------------------------------ kernel-level

@pytest.mark.parametrize("end,starts", [
    (1, (0, 0)),     # first decode step: one live position
    (17, (0, 0)),    # window crosses a block boundary
    (9, (2, 5)),     # left-padded rows, short prefix
    (64, (3, 0)),    # full cache live
])
def test_kernel_matches_dense_window(end, starts):
    rng = np.random.RandomState(0)
    b, h, d, cache_len = 2, 4, 32, 64
    q = jnp.asarray(rng.randn(b, 1, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, cache_len, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, cache_len, h, d), jnp.float32)
    st = jnp.asarray(starts, jnp.int32)
    out = flash_decode_attention(
        q, _fold(k), _fold(v), end=jnp.asarray(end, jnp.int32), starts=st,
        block_k=16, block_major=32,
    )
    ref = _dense_window_attention(q, k, v, end, st)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=_TOL, atol=_TOL)


def test_kernel_traced_end_under_jit():
    """``end`` is the while_loop counter in real decode — must work traced."""
    rng = np.random.RandomState(1)
    b, h, d, cache_len = 1, 2, 16, 32
    q = jnp.asarray(rng.randn(b, 1, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, cache_len, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, cache_len, h, d), jnp.float32)
    fn = jax.jit(lambda e: flash_decode_attention(q, _fold(k), _fold(v),
                                                  end=e))
    for end in (1, 7, 32):
        ref = _dense_window_attention(
            q, k, v, end, jnp.zeros((b,), jnp.int32))
        np.testing.assert_allclose(
            np.asarray(fn(jnp.asarray(end, jnp.int32))), np.asarray(ref),
            rtol=_TOL, atol=_TOL, err_msg=f"end={end}")


def _rows(n_row, *rows):
    """Block tables of ``n_row`` pages a lane from each lane's leading
    physical pages (page 0, the engine's trash page, fills the rest)."""
    tables = np.zeros((len(rows), n_row), np.int32)
    for i, pages in enumerate(rows):
        tables[i, :len(pages)] = pages
    return tables


# name -> (block tables, ends, starts). At page 16 and the default block_k
# a grid step of the bf16 pool covers P = min(16, pages a row) pages (the
# int8 pool of 16-row pages keeps P = 1: module docstring "Paged variant").
# Interpreted, a case costs what TRACING its step's P unrolled page copies
# costs (3.1 s of lowering at P = 16, 0.25 s of running: my measurement, PR
# 58), whatever its rows, lanes or heads, so off the chip the long rows take
# ``_INTERPRETED_BLOCK_K`` = 64 rows a step, P = 4: every window below lies
# in a 64-row block as it lies in a 256-row one (260 = 4 x 64 + 4, 512 =
# 8 x 64, 300 inside a block, 70 = 17 x 4 + 2).
_PAGED_CASES = {
    # 6 pages a row (P = 6, one block a lane): shared prefix pages, a
    # left-padded row, a lane on the trash page
    "row6": (_rows(6, [1, 2, 3, 4, 5, 6], [1, 2, 7, 8],
                   [9, 10, 11, 12, 13, 14], [15, 16, 17, 18]),
             [96, 50, 81, 17], [0, 0, 5, 0]),
    # 80 pages a row (P = 16, five blocks; at P = 4, twenty): windows that
    # end inside a block's first page (257 + 3), on a block's edge (512) and
    # in a last partial page; ``starts`` inside a block; shared prefix
    "row80": (_rows(80, range(1, 18), range(18, 50),
                    list(range(1, 9)) + list(range(50, 122)),
                    range(130, 160)),
              [260, 512, 1275, 470], [0, 0, 0, 300]),
    # today's free lane (engine.py: zero table, ``end`` = the row's
    # length) beside busy ones, at the chat cell's 64 pages a row
    "free64": (_rows(64, range(1, 28), [], range(28, 40)),
               [430, 1024, 177], [0, 0, 0]),
    # a width P does not divide (70 = 4 x 16 + 6 = 17 x 4 + 2): the last
    # block is short
    "row70": (_rows(70, range(1, 71), range(71, 76)),
              [1120, 66], [0, 17]),
}


_INTERPRETED_BLOCK_K = 64


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("h,d", [(16, 64), (8, 128)])
@pytest.mark.parametrize("case", list(_PAGED_CASES))
def test_paged_kernel_at_engine_shapes(case, h, d, kv_dtype):
    """The serving engine's own shapes (page 16, bf16 queries, 16 heads of
    64 / 8 of 128, shared prefix pages, ragged windows) against the dense
    window reference — the case chip runs certify numerically
    (FLEETX_TEST_PLATFORM=real), since a 16-row page sits below the
    packed int8 tile and Mosaic pads it."""
    rng = np.random.RandomState(2)
    tables, ends, starts = _PAGED_CASES[case]
    (b, n_row), ps = tables.shape, 16
    n_pages = int(tables.max()) + 1
    q = jnp.asarray(rng.randn(b, 1, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(n_pages, ps, h, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(n_pages, ps, h, d), jnp.bfloat16)
    tables = jnp.asarray(tables)
    ends = jnp.asarray(ends, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    scales = {}
    if kv_dtype == "int8":
        k8, ks = quantize_kv(k)
        v8, vs = quantize_kv(v)
        scales = dict(k_scale=ks[..., 0], v_scale=vs[..., 0])
        kern_k, kern_v = k8, v8
        k = dequantize_kv(k8, ks)          # the reference sees what the
        v = dequantize_kv(v8, vs)          # kernel reconstructs, in f32
    else:
        kern_k, kern_v = k, v
    # (row6 keeps its step of the table's width; the chip the engine's 16)
    block_k = (None if case == "row6" or jax.default_backend() == "tpu"
               else _INTERPRETED_BLOCK_K)
    out = flash_decode_paged_attention(
        q, _fold(kern_k), _fold(kern_v), tables=tables, end=ends,
        starts=starts, block_k=block_k, **scales)
    gather = lambda x: x[tables].reshape(b, n_row * ps, h, d)
    ref = _dense_window_attention(
        q.astype(jnp.float32), gather(k).astype(jnp.float32),
        gather(v).astype(jnp.float32), ends, starts)
    assert out.dtype == jnp.bfloat16 and out.shape == (b, 1, h, d)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("page,block_k,lanes,n_row,pages", [
    (16, None, 2048, 64, 16),   # the full-head serve cells: 256 rows a step
    (16, None, 2048, 6, 6),     # capped at the table's width
    (16, None, 1024, 3200, 32),  # Trinity's row: 512 rows
    (16, None, 512, 800, 64),   # SmallThinker's and LFM2's: 1,024 rows
    (16, None, 512, 19, 19),
    (16, None, 128, 64, 64),    # Jamba2's row: the table
    (16, None, 4096, 64, 16),   # a wider row: block_k rows, never fewer
    (16, 128, 2048, 80, 8),     # FLEETX_DECODE_BLOCK_K=128: half the rows
    (16, 128, 512, 80, 32),
    (32, None, 2048, 64, 8),
    (256, None, 512, 64, 1),    # a page is block_k rows already
    (512, None, 512, 64, 1),
])
def test_pages_per_step(page, block_k, lanes, n_row, pages):
    """P follows from the shapes a call sees: block_k rows of a row of
    2,048 bf16 lanes over the page size, as many more of a narrower row as
    hold the same bytes, capped at the table's width, and 1 where a page of
    some cache operand is below its dtype's packed tile."""
    pool = lambda dt, width=lanes: jax.ShapeDtypeStruct((3, page, width), dt)
    bf16 = [pool(jnp.bfloat16)] * 2
    assert _pages_per_step(page, n_row, bf16, block_k) == pages
    assert paged_grid(bf16, n_row, block_k) == (pages, -(-n_row // pages))
    int8 = [pool(jnp.int8)] * 2 + [pool(jnp.float32, lanes // 128)] * 2
    got = _pages_per_step(page, n_row, int8, block_k)
    assert got == 1 if page < 32 else got >= pages


@pytest.mark.parametrize("max_live,steps", [
    (None, 13), (4096, 5), (4097, 6), (1024, 2), (1, 2), (20000, 13)])
def test_paged_grid_walks_what_the_bound_can_touch(max_live, steps):
    """The steps of a lane at SmallThinker's row (1,024 rows a step, a table
    of 800 pages): one more than the blocks ``max_live`` rows fill, since a
    window starts anywhere in a block, and never more than the table's."""
    pool = [jax.ShapeDtypeStruct((9, 16, 512), jnp.bfloat16)] * 2
    assert paged_grid(pool, 800, None, max_live) == (64, steps)
    # at one page a step (the int8 pool's 16-row pages) the bound is unused
    int8 = ([jax.ShapeDtypeStruct((9, 16, 512), jnp.int8)] * 2
            + [jax.ShapeDtypeStruct((9, 16, 4), jnp.float32)] * 2)
    assert paged_grid(int8, 800, None, max_live) == (1, 800)


def _step_of_block_k(monkeypatch):
    """Make a grid step of the paged kernel ``block_k`` rows whatever the
    row's width (the kernel gives a narrow row more: ``_FULL_ROW_BYTES``),
    so that a test's small table is several steps."""
    monkeypatch.setattr(da, "_FULL_ROW_BYTES", 0)


# lane -> (pages held, end, start): empty lanes first and last, two in a
# row (2 and 3; with ``starts`` lane 3's window is ``end`` <= ``start`` in a
# later block), busy ones that end in a first block, begin in a later one
# and fill the row
_EMPTY_LANE_WINDOWS = ((0, 0, 0), (10, 300, 0), (0, 0, 0), (10, 100, 300),
                       (3, 70, 0), (10, 290, 270), (20, 640, 0), (0, 0, 0))


@pytest.mark.parametrize("with_starts", [False, True])
@pytest.mark.parametrize("kv_dtype,group", [
    ("bfloat16", 1), ("bfloat16", 4), ("int8", 1)])  # (int8 takes group 1)
@pytest.mark.parametrize("pages", [3, 1])
def test_paged_kernel_with_free_and_empty_lanes_interleaved(
        monkeypatch, pages, kv_dtype, group, with_starts):
    """A lane whose window is empty (``end`` = 0, or ``end`` <= ``start``:
    a lane that decodes no token) is no step and no copy, and its output
    block is EXACT zeros, under a NaN prefill that a block the kernel
    never writes keeps (``chip_smoke.nan_prefilled_outputs``). Between,
    before and behind busy lanes it does not break the chain in which a
    live step starts the next live step's copies: the busy lanes read their
    own pages, and equal bit for bit a call that holds the busy lanes
    alone. At several pages a step (``pages`` 3 of 32 rows: a step's unrolled
    copies are what an interpreted case costs, and 3 keep what 8 showed, a
    window that ends in the first block, windows that begin in a later one,
    a short last block: 20 = 6 x 3 + 2) and at one (``block_k`` = the page
    size), over a bf16 and an int8 pool, with every key head its own and
    shared by 4 query heads."""
    from chip_smoke import nan_prefilled_outputs

    rng = np.random.RandomState(5)
    if (kv_dtype, pages, jax.default_backend()) == ("int8", 3, "tpu"):
        pytest.skip("Mosaic refuses the copy of a scale page ([rows, heads]: "
                    "no 128 lanes) at several pages a step, on any tree; "
                    "the engine's 16-row int8 pages take one a step")
    # (on the chip a copied row is whole 128-lane tiles: 2 key heads of 64)
    b, ps, h, d, n_row = len(_EMPTY_LANE_WINDOWS), 32, 8, 64, 20
    kvh = h // group
    held = np.cumsum([0] + [n for n, _, _ in _EMPTY_LANE_WINDOWS])
    tables = jnp.asarray(_rows(n_row, *(
        range(1 + lo, 1 + hi) for lo, hi in zip(held, held[1:]))))
    ends = jnp.asarray([e for _, e, _ in _EMPTY_LANE_WINDOWS], jnp.int32)
    starts = jnp.asarray([s for _, _, s in _EMPTY_LANE_WINDOWS], jnp.int32)
    if not with_starts:
        ends = jnp.where(ends > starts, ends, 0)
        starts = jnp.zeros_like(starts)
    busy = np.flatnonzero(np.asarray(ends > starts))
    assert list(busy) == [1, 4, 5, 6]
    q = jnp.asarray(rng.randn(b, 1, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(int(held[-1]) + 1, ps, kvh, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(int(held[-1]) + 1, ps, kvh, d), jnp.bfloat16)
    scales, pool_k, pool_v = {}, k, v
    if kv_dtype == "int8":
        pool_k, ks = quantize_kv(k)
        pool_v, vs = quantize_kv(v)
        scales = dict(k_scale=ks[..., 0], v_scale=vs[..., 0])
        k, v = dequantize_kv(pool_k, ks), dequantize_kv(pool_v, vs)

    def call(lanes):
        with nan_prefilled_outputs():
            return np.asarray(flash_decode_paged_attention(
                q[lanes], _fold(pool_k), _fold(pool_v), tables=tables[lanes],
                end=ends[lanes], block_k=pages * ps,
                starts=starts[lanes] if with_starts else None, **scales
            ).astype(jnp.float32))

    _step_of_block_k(monkeypatch)
    assert paged_grid([_fold(pool_k)], n_row, pages * ps) == (
        pages, -(-n_row // pages))
    out = call(np.arange(b))
    gather = lambda x: x[tables].reshape(b, n_row * ps, kvh, d)
    ref = _dense_grouped(
        q.astype(jnp.float32), gather(k).astype(jnp.float32),
        gather(v).astype(jnp.float32), ends, starts)
    np.testing.assert_allclose(out[busy], np.asarray(ref)[busy],
                               rtol=3e-2, atol=3e-2)
    empty = np.setdiff1d(np.arange(b), busy)
    assert np.array_equal(out[empty], np.zeros_like(out[empty])), out[empty]
    np.testing.assert_array_equal(out[busy], call(busy))


def _lower_for_tpu(monkeypatch, fn, *args):
    """Lower ``fn`` for the tpu platform with the interpreter off: the
    Pallas TPU lowering's block-shape rules run in Python, so a layout the
    chip's compiler would refuse fails here on the CPU."""
    import fleetx_tpu.ops.pallas.decode_attention as da

    monkeypatch.setattr(da, "_interpret", lambda: False)
    traced = jax.jit(fn).trace(*args)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    return traced, text


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


# cell -> (lanes, pages a row, query heads, key heads, head size, the window
# of the call or None, block_k, the grid's steps a lane): every decode call
# of the seven serve cells that run the kernel, at its real shape
_SERVE_CELL_CALLS = {
    "gpt1.3b-serve-chat-steady-v3": (24, 64, 16, 16, 128, None, None, 4),
    "gpt1.3b-serve-docs-batch": (16, 64, 16, 16, 128, None, None, 4),
    "olmoe-l8-serve-gen-batch": (32, 80, 16, 16, 128, None, None, 5),
    # FLEETX_DECODE_BLOCK_K=128: half the rows a step
    "olmoe-l8-serve-gen-batch.block_k128": (32, 80, 16, 16, 128, None, 128,
                                            10),
    "smallthinker-l8-serve-longdoc-gen.full": (24, 800, 28, 4, 128, None,
                                               None, 13),
    "smallthinker-l8-serve-longdoc-gen.window": (24, 800, 28, 4, 128, 4096,
                                                 None, 5),
    "trinity-l5-serve-mixed-longshort.full": (8, 3200, 48, 8, 128, None,
                                              None, 100),
    "trinity-l5-serve-mixed-longshort.window": (8, 3200, 48, 8, 128, 4096,
                                                None, 9),
    "lfm2-l14-serve-agent-prefix": (48, 304, 32, 8, 64, None, None, 5),
    "jamba2-3b-serve-chat-peak": (256, 64, 20, 1, 128, None, None, 1),
}


def _cell_call(cell):
    """``(fn, argument shapes, steps)`` of one of ``_SERVE_CELL_CALLS``:
    page 16, a bf16 pool, the window layer's ``starts`` and bound."""
    lanes, n_row, h, kv, d, window, block_k, steps = _SERVE_CELL_CALLS[cell]
    spec = jax.ShapeDtypeStruct
    args = (spec((lanes, 1, h, d), jnp.bfloat16),
            spec((lanes * 64 + 1, 16, kv * d), jnp.bfloat16),
            spec((lanes, n_row), jnp.int32), spec((lanes,), jnp.int32))

    def fn(q, pool, tables, ends):
        return flash_decode_paged_attention(
            q, pool, pool, tables=tables, end=ends, block_k=block_k,
            starts=window and jnp.maximum(ends - window, 0), max_live=window)

    return fn, args, steps


@pytest.mark.parametrize("cell", list(_SERVE_CELL_CALLS))
def test_paged_kernel_grid_at_the_serve_cells(monkeypatch, cell):
    """Every serve cell's decode call lowers for the TPU as
    ``fleetx_decode_paged`` with the grid its shapes give: the mechanisms of
    PR 30 and PR 50 have no hit share, they engage on every call of a shape
    or on none, and the grid says which. The three full-head cells (16 heads
    of 128: 2,048 lanes) keep ``(lanes, ceil(pages of a row / 16))``; a
    narrower row takes more rows a step, and a window layer's call walks
    the steps its window can touch and not the table's."""
    fn, args, steps = _cell_call(cell)
    lanes, n_row, _, kv, d, window, block_k, _ = _SERVE_CELL_CALLS[cell]
    if kv * d == 2048 and block_k is None:
        assert steps == -(-n_row // 16)
    assert paged_grid([args[1]], n_row, block_k, window)[1] == steps
    traced, text = _lower_for_tpu(monkeypatch, fn, *args)
    (call,) = _pallas_calls(traced.jaxpr.jaxpr)
    assert call.params["grid_mapping"].grid == (lanes, steps)
    assert f'kernel_name = "{PAGED_KERNEL_NAME}"' in text


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell", [
    c for c, call in _SERVE_CELL_CALLS.items() if call[3] * call[4] < 2048])
def test_narrow_rows_compile_for_the_v5e(one_chip, monkeypatch, cell):
    """The calls whose step PR 50 made longer (rows of 512, 1,024 and 128
    lanes: 64, 32 and 64 pages a step, one tile each) through the chip's own
    compiler, without the chip: Mosaic takes the tile and its four buffer
    halves fit VMEM."""
    fn, args, _ = _cell_call(cell)
    monkeypatch.setattr(da, "_interpret", lambda: False)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in args]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert PAGED_KERNEL_NAME in text


# lane -> (end, what the test says of it) under a window of 100 rows, where a
# step is 64 rows (4 pages of 16) and the table 15 pages: 3.75 steps
_WINDOW_LANES = (
    (237, "starts mid-page and mid-step; its last block, the table's "
          "fourth, runs past the table"),
    (0, "empty"),
    (50, "end below the window: starts at 0"),
    (0, "empty"),
    (0, "empty"),
    (192, "ends on a step's edge"),
    (130, "touches three steps: the most 100 rows can"),
    (64, "one whole first block"),
)


@pytest.mark.parametrize("window", [100, 20])
@pytest.mark.parametrize("group", [7, 6, 4])
def test_paged_kernel_walks_a_window_from_where_it_starts(monkeypatch, group,
                                                          window):
    """A window layer's call (``starts`` = ``end - window``, ``max_live`` =
    the window): step jm of a lane is the block its window starts in plus
    jm, so the grid is as long as the window (3 steps a lane for 100 rows, 2
    for 20: shorter than a step) and not as the table (4). Against the dense
    path over grouped heads (groups of 7, 6 and 4: SmallThinker's, Trinity's
    and LFM2's), with empty lanes between live ones in the chain of copies;
    and bit for bit the call that walks the whole table, whose blocks lie
    where these do."""
    rng = np.random.RandomState(7)
    # (on the chip a copied row is whole 128-lane tiles: 2 key heads of 64)
    ps, n_row, kvh, d, block_k = 16, 15, 2, 64, 64
    b, h = len(_WINDOW_LANES), group * kvh
    ends = jnp.asarray([e for e, _ in _WINDOW_LANES], jnp.int32)
    starts = jnp.maximum(ends - window, 0)
    q = jnp.asarray(rng.randn(b, 1, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b * n_row + 1, ps, kvh, d), jnp.float32)
    v = jnp.asarray(rng.randn(b * n_row + 1, ps, kvh, d), jnp.float32)
    tables = jnp.asarray(
        1 + rng.permutation(b * n_row).reshape(b, n_row), jnp.int32)
    _step_of_block_k(monkeypatch)
    assert paged_grid([_fold(k)], n_row, block_k, window) == (
        4, {100: 3, 20: 2}[window])

    def call(max_live):
        return np.asarray(flash_decode_paged_attention(
            q, _fold(k), _fold(v), tables=tables, end=ends, starts=starts,
            block_k=block_k, max_live=max_live))

    got = call(window)
    gather = lambda x: x[tables].reshape(b, n_row * ps, kvh, d)
    want = np.asarray(_dense_grouped(q, gather(k), gather(v), ends, starts))
    busy = np.flatnonzero(np.asarray(ends))
    np.testing.assert_allclose(got[busy], want[busy], rtol=_TOL, atol=_TOL)
    empty = np.setdiff1d(np.arange(b), busy)
    assert np.array_equal(got[empty], np.zeros_like(got[empty]))
    np.testing.assert_array_equal(got, call(None))


@pytest.mark.parametrize("mp", [1, 2])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("h,d", [(16, 64), (8, 128)])
@pytest.mark.parametrize("paged", [False, True])
def test_decode_kernels_lower_for_tpu(monkeypatch, paged, h, d, kv_dtype,
                                      mp):
    """Every decode entry point at the serving engine's shapes (GPT-345M:
    8 lanes, page 16, cache 352; bf16 and int8 caches; head_dim 64 and
    128; bare and under the mp2 shard_map) must pass the TPU lowering.
    The bf16 pool at 22 pages a row takes P = 16 pages a step; the int8
    pool keeps P = 1, the honest answer while a 16-row int8 page is half
    a packed (32, 128) tile (``test_pages_per_step``).
    The seed's ``[.., len, h, d]`` blocks squeezed the sublane axis and
    were refused in every one of these combinations while twenty PRs of
    interpret-mode tests stayed green."""
    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = (build_mesh(MeshConfig(mp=2), jax.devices()[:2]) if mp == 2
            else None)
    b, ps, cache_len = 8, 16, 352
    lead = (b * cache_len // ps + 1, ps) if paged else (b, cache_len)
    q = jnp.zeros((b, 1, h, d), jnp.bfloat16)
    kv = jnp.zeros(lead + (h * d,), kv_dtype)
    scales = {}
    if kv_dtype == jnp.int8:
        sc = jnp.ones(lead + (h,), jnp.float32)
        scales = dict(k_scale=sc, v_scale=sc)
    ends = jnp.full((b,), 40, jnp.int32)
    if paged:
        tables = jnp.zeros((b, cache_len // ps), jnp.int32)
        fn = lambda q, kv, e: flash_decode_paged_attention(
            q, kv, kv, tables=tables, end=e, mesh=mesh, **scales)
    else:
        fn = lambda q, kv, e: flash_decode_attention(
            q, kv, kv, end=e, mesh=mesh, **scales)
    _lower_for_tpu(monkeypatch, fn, q, kv, ends)


def _dense_grouped(q, k, v, end, starts):
    """The dense path over grouped heads: query head r against key head
    ``r // group`` (keys repeated for the reference, which the kernels never
    do), the window ``[starts, end)`` of each lane."""
    group = q.shape[2] // k.shape[2]
    return _dense_window_attention(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2), end,
        starts)


@pytest.mark.parametrize("path", ["contiguous", "paged_page", "paged_block"])
@pytest.mark.parametrize("heads,kv_heads", [(28, 4), (8, 2), (4, 4),
                                            (20, 1)])
def test_grouped_heads_match_the_dense_path(path, heads, kv_heads):
    """Grouped-query heads through the three kernel paths (the contiguous
    kernel, the paged kernel at one page a step, and at several), at group
    7 (28 rows against the lanes of 4 key heads: SmallThinker's), 4 and 1,
    and 20 over ONE key head (Jamba2's: 20 rows against one head's lanes),
    with ``starts`` from a window (``end - 24``) beside rows read from 0."""
    rng = np.random.RandomState(3)
    b, d, ps, n_row = 3, 16, 8, 12
    q = jnp.asarray(rng.randn(b, 1, heads, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, n_row * ps, kv_heads, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, n_row * ps, kv_heads, d), jnp.float32)
    ends = jnp.asarray([5, 61, 96], jnp.int32)
    starts = jnp.asarray([0, 61 - 24, 0], jnp.int32)
    want = _dense_grouped(q, k, v, ends, starts)
    if path == "contiguous":
        got = flash_decode_attention(q, _fold(k), _fold(v), end=ends,
                                     starts=starts, block_k=16, block_major=32)
    else:
        # every lane's rows scattered over a shared pool through its table
        order = 1 + rng.permutation(b * n_row).reshape(b, n_row)
        pool_k = np.zeros((b * n_row + 1, ps, kv_heads * d), np.float32)
        pool_v = np.zeros_like(pool_k)
        for lane in range(b):
            pool_k[order[lane]] = np.asarray(_fold(k))[lane].reshape(
                n_row, ps, -1)
            pool_v[order[lane]] = np.asarray(_fold(v))[lane].reshape(
                n_row, ps, -1)
        got = flash_decode_paged_attention(
            q, jnp.asarray(pool_k), jnp.asarray(pool_v),
            tables=jnp.asarray(order, jnp.int32), end=ends, starts=starts,
            block_k=ps if path == "paged_page" else 2 * ps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=_TOL, atol=_TOL)


def test_grouped_heads_take_no_scales_and_no_mesh():
    q = jnp.zeros((2, 1, 8, 16), jnp.float32)
    kv = jnp.zeros((2, 32, 2 * 16), jnp.int8)
    scale = jnp.ones((2, 32, 2), jnp.float32)
    with pytest.raises(NotImplementedError, match="grouped"):
        flash_decode_attention(q, kv, kv, end=jnp.asarray(4, jnp.int32),
                               k_scale=scale, v_scale=scale)
    with pytest.raises(ValueError, match="kv_heads"):
        flash_decode_attention(q, jnp.zeros((2, 32, 3 * 16)),
                               jnp.zeros((2, 32, 3 * 16)),
                               end=jnp.asarray(4, jnp.int32))


@pytest.mark.parametrize("paged", [False, True])
def test_grouped_kernels_lower_for_tpu_at_the_smallthinker_cell(monkeypatch,
                                                                paged):
    """``smallthinker-l8-serve-longdoc-gen``'s decode call (24 lanes, 28
    query heads over 4 key heads of 128, page 16, 800 pages a row, a bf16
    pool of both classes) passes the TPU lowering as the block kernel, 64
    pages a step; the contiguous kernel at the same heads too."""
    lanes, h, kv, d, ps, n_row = 24, 28, 4, 128, 16, 800
    q = jnp.zeros((lanes, 1, h, d), jnp.bfloat16)
    ends = jnp.full((lanes,), 6000, jnp.int32)
    starts = ends - 4096
    if paged:
        pool = jnp.zeros((4096, ps, kv * d), jnp.bfloat16)
        tables = jnp.zeros((lanes, n_row), jnp.int32)
        fn = lambda q, kv_, t, e, s: flash_decode_paged_attention(
            q, kv_, kv_, tables=t, end=e, starts=s)
        traced, text = _lower_for_tpu(monkeypatch, fn, q, pool, tables, ends,
                                      starts)
        (call,) = _pallas_calls(traced.jaxpr.jaxpr)
        assert call.params["grid_mapping"].grid == (lanes, -(-n_row // 64))
        assert f'kernel_name = "{PAGED_KERNEL_NAME}"' in text
    else:
        cache = jnp.zeros((lanes, 1024, kv * d), jnp.bfloat16)
        fn = lambda q, c, e, s: flash_decode_attention(q, c, c, end=e,
                                                       starts=s)
        _lower_for_tpu(monkeypatch, fn, q, cache, ends // 8, starts // 8)


def test_grouped_kernel_lowers_for_tpu_at_the_jamba2_cell(monkeypatch):
    """``jamba2-3b-serve-chat-peak``'s decode call (256 lanes, 20 query
    heads over ONE key head of 128, page 16, 64 pages a row, a bf16 pool of
    its two attention layers) passes the TPU lowering as the block kernel,
    the 64 pages of a row in one step."""
    lanes, h, d, ps, n_row = 256, 20, 128, 16, 64
    q = jnp.zeros((lanes, 1, h, d), jnp.bfloat16)
    pool = jnp.zeros((2 * 16385, ps, d), jnp.bfloat16)
    tables = jnp.zeros((lanes, n_row), jnp.int32)
    ends = jnp.full((lanes,), 700, jnp.int32)
    fn = lambda q, kv_, t, e: flash_decode_paged_attention(
        q, kv_, kv_, tables=t, end=e)
    traced, text = _lower_for_tpu(monkeypatch, fn, q, pool, tables, ends)
    (call,) = _pallas_calls(traced.jaxpr.jaxpr)
    assert call.params["grid_mapping"].grid == (lanes, 1)
    assert f'kernel_name = "{PAGED_KERNEL_NAME}"' in text


def test_fit_decode_blocks():
    assert fit_decode_blocks(1024) == (256, 1024)
    assert fit_decode_blocks(16) == (16, 16)
    bk, major = fit_decode_blocks(40)
    assert bk is not None and 40 % bk == 0 and major % bk == 0
    assert fit_decode_blocks(100) == (None, None)  # not a multiple of 8


def test_supported_requires_tileable_cache(monkeypatch):
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    assert decode_flash_supported(64)
    assert not decode_flash_supported(100)
    monkeypatch.delenv("FLEETX_FORCE_FLASH")
    assert not decode_flash_supported(64)  # CPU backend, no force


# ------------------------------------------------- generation-loop parity

def _gen_both_paths(model, params, prompt, cfg, monkeypatch, *, rng=None,
                    attention_mask=None):
    """(dense_tokens, flash_tokens, flash_call_count) for one decode run."""
    import fleetx_tpu.ops.pallas.decode_attention as da

    monkeypatch.delenv("FLEETX_FORCE_FLASH", raising=False)
    dense = np.asarray(generate(model, params, prompt, cfg, rng=rng,
                                attention_mask=attention_mask))

    calls = {"n": 0}
    orig = flash_decode_attention

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    monkeypatch.setattr(da, "flash_decode_attention", counting)
    flash = np.asarray(generate(model, params, prompt, cfg, rng=rng,
                                attention_mask=attention_mask))
    return dense, flash, calls["n"]


def test_greedy_parity_flash_vs_dense(monkeypatch, model_and_params):
    model, params = model_and_params
    prompt = jnp.asarray(np.random.RandomState(1).randint(0, 97, (2, 6)),
                         jnp.int32)
    cfg = GenerationConfig(max_length=8, min_length=8,
                           decode_strategy="greedy",
                           eos_token_id=10**6, pad_token_id=96)
    dense, flash, n = _gen_both_paths(model, params, prompt, cfg, monkeypatch)
    assert n > 0, "flash-decode fast path never engaged"
    np.testing.assert_array_equal(dense, flash)


@pytest.mark.slow  # 10.4s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_sampling_parity_flash_vs_dense(monkeypatch, model_and_params):
    """Fixed-rng sampling with every scalar post-process on (temperature,
    top-k, top-p, repetition penalty) must be byte-identical across paths —
    the logits feeding _sample agree to the last ulp only if the kernel
    matches the dense math that tightly."""
    model, params = model_and_params
    prompt = jnp.asarray(np.random.RandomState(2).randint(0, 97, (2, 5)),
                         jnp.int32)
    cfg = GenerationConfig(max_length=7, min_length=7,
                           decode_strategy="sampling", temperature=0.8,
                           top_k=12, top_p=0.9, repetition_penalty=1.2,
                           eos_token_id=10**6, pad_token_id=96)
    dense, flash, n = _gen_both_paths(model, params, prompt, cfg, monkeypatch,
                                      rng=jax.random.PRNGKey(7))
    assert n > 0
    np.testing.assert_array_equal(dense, flash)


def test_left_padded_prompt_parity(monkeypatch, model_and_params):
    """Left-padded rows exercise the kernel's per-row ``starts`` window."""
    model, params = model_and_params
    padded = jnp.asarray([[96, 96, 5, 17, 3], [7, 11, 13, 19, 23]], jnp.int32)
    mask = jnp.asarray([[0, 0, 1, 1, 1], [1, 1, 1, 1, 1]], jnp.int32)
    cfg = GenerationConfig(max_length=6, min_length=6,
                           decode_strategy="greedy",
                           eos_token_id=10**6, pad_token_id=96)
    dense, flash, n = _gen_both_paths(model, params, padded, cfg, monkeypatch,
                                      attention_mask=mask)
    assert n > 0
    np.testing.assert_array_equal(dense, flash)


@pytest.mark.slow  # 6.4s (PR 15 tier-1 budget audit): flash-vs-dense
# decode parity stays tier-1 via the greedy/sampling/left-padded gates
# above; beam semantics stay tier-1 in test_beam_search.py (beam's
# flash variant re-runs with the slow-marked beam left-pad parity)
def test_beam_search_parity_flash_vs_dense(monkeypatch, model_and_params):
    """beam_search() rides the same model decode branch — free fast path."""
    model, params = model_and_params
    prompt = jnp.asarray(np.random.RandomState(4).randint(0, 97, (2, 4)),
                         jnp.int32)
    cfg = GenerationConfig(max_length=5, min_length=5,
                           decode_strategy="beam_search", num_beams=3,
                           length_penalty=1.0, eos_token_id=10**6,
                           pad_token_id=96)
    dense, flash, n = _gen_both_paths(model, params, prompt, cfg, monkeypatch)
    assert n > 0
    np.testing.assert_array_equal(dense, flash)


def test_untileable_cache_falls_back_dense(monkeypatch, model_and_params):
    """A preset decode_cache_len that doesn't tile must not crash — the
    model routes to the dense path (decode_flash_supported pre-screen)."""
    import dataclasses

    import fleetx_tpu.ops.pallas.decode_attention as da

    model, params = model_and_params
    model = model.clone(cfg=dataclasses.replace(model.cfg,
                                                decode_cache_len=13))
    calls = {"n": 0}
    orig = flash_decode_attention

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    monkeypatch.setattr(da, "flash_decode_attention", counting)
    cfg = GenerationConfig(max_length=5, decode_strategy="greedy",
                           eos_token_id=10**6, pad_token_id=96)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    out = generate(model, params, prompt, cfg)
    assert calls["n"] == 0  # 13 is not a multiple of 8: dense fallback
    assert out.shape == (1, 8)


@pytest.mark.parametrize("path", ["contiguous", "paged_page", "paged_block"])
def test_group_4_at_head_size_64_matches_the_dense_path(path):
    """LFM2's heads (32 query heads over 8 key heads of 64: 512 lanes a row,
    group 4) through the three kernel paths against the dense path."""
    rng = np.random.RandomState(5)
    b, heads, kv_heads, d, ps, n_row = 3, 32, 8, 64, 16, 6
    q = jnp.asarray(rng.randn(b, 1, heads, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, n_row * ps, kv_heads, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, n_row * ps, kv_heads, d), jnp.float32)
    ends = jnp.asarray([7, 61, 96], jnp.int32)
    want = _dense_grouped(q, k, v, ends, jnp.zeros_like(ends))
    if path == "contiguous":
        got = flash_decode_attention(q, _fold(k), _fold(v), end=ends,
                                     block_k=16, block_major=32)
    else:
        order = 1 + rng.permutation(b * n_row).reshape(b, n_row)
        pool_k = np.zeros((b * n_row + 1, ps, kv_heads * d), np.float32)
        pool_v = np.zeros_like(pool_k)
        for lane in range(b):
            pool_k[order[lane]] = np.asarray(_fold(k))[lane].reshape(
                n_row, ps, -1)
            pool_v[order[lane]] = np.asarray(_fold(v))[lane].reshape(
                n_row, ps, -1)
        got = flash_decode_paged_attention(
            q, jnp.asarray(pool_k), jnp.asarray(pool_v),
            tables=jnp.asarray(order, jnp.int32), end=ends,
            block_k=ps if path == "paged_page" else 2 * ps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=_TOL, atol=_TOL)


@pytest.mark.parametrize("paged", [False, True])
def test_grouped_kernels_lower_for_tpu_at_the_lfm2_cell(monkeypatch, paged):
    """``lfm2-l14-serve-agent-prefix``'s decode call (48 lanes, 32 query
    heads over 8 key heads of 64, page 16, 304 pages a row, the bf16 pool of
    its three attention layers) passes the TPU lowering as the block kernel,
    64 pages a step; the contiguous kernel at the same heads too."""
    lanes, h, kv, d, ps, n_row = 48, 32, 8, 64, 16, 304
    q = jnp.zeros((lanes, 1, h, d), jnp.bfloat16)
    ends = jnp.full((lanes,), 4000, jnp.int32)
    if paged:
        pool = jnp.zeros((3 * (lanes * n_row + 1), ps, kv * d), jnp.bfloat16)
        tables = jnp.zeros((lanes, n_row), jnp.int32)
        fn = lambda q, kv_, t, e: flash_decode_paged_attention(
            q, kv_, kv_, tables=t, end=e)
        traced, text = _lower_for_tpu(monkeypatch, fn, q, pool, tables, ends)
        (call,) = _pallas_calls(traced.jaxpr.jaxpr)
        assert call.params["grid_mapping"].grid == (lanes, -(-n_row // 64))
        assert f'kernel_name = "{PAGED_KERNEL_NAME}"' in text
    else:
        cache = jnp.zeros((lanes, 1024, kv * d), jnp.bfloat16)
        fn = lambda q, c, e: flash_decode_attention(q, c, c, end=e)
        _lower_for_tpu(monkeypatch, fn, q, cache, ends // 8)
