"""The prefill kernel over grouped heads (``ops/pallas/prefill_gqa.py``,
``fleetx_prefill_gqa``) interpreted on the CPU at the published head size
and a key block of 256 rows (a quarter of the kernel's own: a chunk stands
in a lane of four blocks, under a window of one, as it does at 1,024, and
the dense twin's products are a sixteenth), against its plain twin
``hybrid.grouped_attention`` over the same gathered rows: full and window
layers, every place a chunk can stand in its lane, rows past the chunk and
before the window poisoned, what chooses the kernel, the model's chunks
through both classes of page through it, and the span fields that count its
key rows at the kernel's own block. (Compiled for a described v5e at the
published widths and the kernel's own block under the one topology fixture
of ``tests/test_axk1_serving.py``.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_smallthinker_serving as st  # sibling module (rootdir import)
from fleetx_tpu.models.gpt import hybrid
from fleetx_tpu.models.gpt.model import GPTConfig
from fleetx_tpu.ops.pallas import prefill_gqa
from perfbench.drivers.serve_closed_loop_swa import Served
from perfbench.reference import smallthinker_f32

D, PAGE = 128, 16
BLOCK = 256                         # interpreted (``interpreted_block``)
WINDOW, LANE = BLOCK, 4 * BLOCK     # a lane of four key blocks
GROUPS = {"7_over_1": (14, 2), "20_over_1": (20, 1)}   # heads, key heads
# a float32 sum in another order; one bfloat16 step of values near 2
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
CFG = GPTConfig.from_model_config(dict(
    st.MODEL, head_size=D, sliding_window=WINDOW, max_position_embeddings=LANE,
    decode_cache_len=LANE, decode_page_size=PAGE))
# the same at the kernel's own block: what the span fields count
OWN_WINDOW, OWN_LANE = prefill_gqa.BLOCK_ROWS, 4 * prefill_gqa.BLOCK_ROWS
OWN = dataclasses.replace(CFG, sliding_window=OWN_WINDOW,
                          max_position_embeddings=OWN_LANE,
                          decode_cache_len=OWN_LANE)


@pytest.fixture()
def interpreted_block(monkeypatch):
    """Key blocks of ``BLOCK`` rows while ``_kernel`` is traced and run."""
    monkeypatch.setattr(prefill_gqa, "BLOCK_ROWS", BLOCK)


def operands(s, heads, kv_heads, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (s, heads, D), dtype),
            jax.random.normal(k[1], (LANE, kv_heads * D), dtype),
            jax.random.normal(k[2], (LANE, kv_heads * D), dtype))


def gathered(kind, start, s):
    """``(base, rows, window)``: what the layer of ``kind`` gathers of the
    lane for a chunk of ``s`` rows at ``start`` (``hybrid``'s own
    geometry)."""
    if kind == "full":
        return 0, LANE, None
    pages, first = hybrid.window_gather(CFG, start, s, LANE // PAGE)
    return int(first) * PAGE, pages * PAGE, WINDOW


@jax.jit
def _twin(q, k, v, start, base, window):
    q_pos = start + jnp.arange(q.shape[0], dtype=jnp.int32)[:, None]
    k_pos = base + jnp.arange(k.shape[0], dtype=jnp.int32)[None, :]
    allowed = (k_pos <= q_pos) & (q_pos - k_pos < window)
    return hybrid.grouped_attention(q[None], k[None], v[None],
                                    allowed[None, None])[0]


_kernel = jax.jit(prefill_gqa.prefill_gqa, static_argnames="window")


def both(kind, start, q, k, v, poison=False):
    base, rows, window = gathered(kind, start, q.shape[0])
    # the trash page's rows behind the last, to whole key blocks
    more = jnp.ones((prefill_gqa.padded_rows(rows) - rows, k.shape[1]),
                    k.dtype)
    k, v = (jnp.concatenate([x[base:base + rows], more]) for x in (k, v))
    want = _twin(q, k, v, start, base, window or 1 << 30)
    if poison:  # every row no query of the chunk sees
        at = base + jnp.arange(k.shape[0])[:, None]
        dead = (at >= start + q.shape[0]) | (at <= start - (window or LANE))
        assert bool(dead.any())
        k, v = (jnp.where(dead, jnp.nan, x) for x in (k, v))
    got = _kernel(q, k, v, jnp.int32(start), jnp.int32(base), window=window)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


STARTS = {"first": lambda s: 0, "inside_the_first_block": lambda s: 24,
          "block_edge": lambda s: BLOCK, "past_the_window": lambda s: 650,
          "last_chunk": lambda s: LANE - s}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("s", [64, 128])
@pytest.mark.parametrize("start", sorted(STARTS))
@pytest.mark.parametrize("kind", ["full", "window"])
def test_the_kernel_is_grouped_attention(interpreted_block, kind, start, s,
                                         group, dtype):
    start = STARTS[start](s)
    got, want = both(kind, start, *operands(s, *GROUPS[group], dtype,
                                            seed=start + s))
    assert np.abs(got - want).max() < TOL[dtype]


@pytest.mark.parametrize("start", ["inside_the_first_block", "block_edge",
                                   "past_the_window"])
@pytest.mark.parametrize("kind", ["full", "window"])
def test_rows_no_query_sees_change_nothing_whatever_they_hold(
        interpreted_block, kind, start):
    """The rows past the chunk, in its last live block and in every block
    behind it, and in a window layer the rows before the first query's
    window (a released page's entry points at the trash page), as NaN: not
    a bit of the output moves."""
    args = operands(64, 14, 2, jnp.float32)
    clean, _ = both(kind, STARTS[start](64), *args)
    poisoned, _ = both(kind, STARTS[start](64), *args, poison=True)
    assert np.isfinite(clean).all()
    assert (poisoned == clean).all()


@pytest.mark.parametrize("changes, b, s, forced, taken", [
    ({}, 1, 16, "1", True),
    (dict(head_size=64), 1, 16, "1", False),
    ({}, 2, 16, "1", False),
    ({}, 1, 1, "1", False),
    (dict(use_flash_attention=False), 1, 16, "1", False),
    ({}, 1, 16, None, False)],
    ids=["kernel", "head_size_64", "two_lanes", "one_row", "configured_off",
         "no_kernels_here"])
def test_the_kernel_is_chosen_by_what_the_call_is(changes, b, s, forced,
                                                  taken, monkeypatch):
    if forced:
        monkeypatch.setenv("FLEETX_FORCE_FLASH", forced)
    else:
        monkeypatch.delenv("FLEETX_FORCE_FLASH", raising=False)
    cfg = dataclasses.replace(CFG, **{"use_flash_attention": True, **changes})
    assert hybrid.HybridSelfAttention(cfg)._chunk_kernel(b, s) is taken


# ------------------------------------------------- through the model's pool

MODEL = dict(st.MODEL, num_attention_heads=4, num_key_value_heads=2,
             head_size=D)


@pytest.fixture(scope="module")
def variables():
    """``test_smallthinker_serving``'s seeded weights at a head of 128."""
    return st.seeded(st.build(**MODEL))


@pytest.mark.parametrize("flash, calls", [(True, True), (False, False)],
                         ids=["kernel", "plain_twin"])
def test_chunked_prefill_then_decode_through_both_page_classes(
        variables, flash, calls, monkeypatch):
    """``test_smallthinker_serving``'s 44 tokens in chunks of 16 and 12
    decoded, past the window, with the kernels on and key blocks of 32 rows
    (a lane of four, a window layer's gather of two): the logits are the
    float32 reference's, and the chunks did run the kernel."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    monkeypatch.setattr(prefill_gqa, "BLOCK_ROWS", 32)
    traced = []
    real = prefill_gqa.prefill_gqa
    monkeypatch.setattr(prefill_gqa, "prefill_gqa",
                        lambda *a, **k: traced.append(1) or real(*a, **k))
    engine = st.engine_of.__wrapped__(
        st.build(**MODEL, use_flash_attention=flash), variables)
    row = st.TOKENS[0]
    mine = Served(engine, st.CHUNK).sequence(row, 44)
    want = np.asarray(smallthinker_f32.configured(MODEL)(
        variables["params"], row))
    assert bool(traced) is calls
    assert st.distance(mine["logits"], want[-28:]) <= st.TOL
    assert engine.cache_manager.window_pool.recycled > 0


# ------------------------------------------------------------ span fields

@pytest.mark.parametrize("rows, behind, full, window", [
    (512, 0, 1024, 1024), (512, 512, 1024, 1024), (256, 1024, 2048, 2048),
    (512, 1536, 2048, 2048), (100, 1900, 3072, 2048)])
def test_a_chunks_span_fields_count_the_kernels_key_rows(rows, behind, full,
                                                         window):
    """Whole 1,024-row blocks from the one that holds the first query's
    oldest visible key (counted from the first row gathered) to the one
    that holds the program's last row; and the program's rows of queries,
    padding included (``perfbench/flops_gqa_prefill.py`` counts a step's
    work from them)."""
    program = -(-rows // 256) * 256
    fields = OWN.spans(rows, behind, program)
    assert fields == {"attn_full_key_rows": full,
                      "attn_window_key_rows": window,
                      "attn_query_rows": program}
    small = dataclasses.replace(OWN, head_size=64)
    assert small.spans(rows, behind) == {}
    assert GPTConfig().spans(rows, behind) == {}
    one_kind = dataclasses.replace(OWN, sliding_window=None,
                                   sliding_window_layout=None)
    assert one_kind.spans(rows, behind, 512) == {
        "attn_query_rows": 512,
        "attn_full_key_rows": prefill_gqa.key_rows(behind, 512, 0, None,
                                                   OWN_LANE)}


def test_the_engine_threads_the_span_fields(variables, monkeypatch):
    from fleetx_tpu.obs.tracing import get_recorder

    engine = st.engine_of(st.build(**MODEL), variables)
    engine.submit(st.TOKENS[0][:40], max_length=2)
    engine.drain()
    chunks = [s.attrs for s in get_recorder().spans()
              if s.name == "serving.prefill_chunk"][-3:]
    assert [c["start"] for c in chunks] == [0, 16, 32]
    # fewer rows than a key block of the kernel's own size are one block:
    # the lane's 128, and a window layer's gather of 5 pages of 8 (4 for
    # the last chunk's program of 8 rows)
    assert [(c["attn_full_key_rows"], c["attn_window_key_rows"])
            for c in chunks] == [(128, 40), (128, 40), (128, 32)]
