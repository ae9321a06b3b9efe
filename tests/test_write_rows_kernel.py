"""``fleetx_write_rows`` (ops/pallas/write_rows.py), the key/value write a
stack of layer kinds branches on, against the scatter it stands in for
(models/gpt/paged_write.py ``_by_row`` / ``_by_page``): the same bits in the
same places outside the trash page, kept or dropped. Interpreted, at the
smallest shapes that take each path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt import paged_write
from fleetx_tpu.ops.pallas import write_rows as writer

PS, PAGES, TABLE = 8, 128, 6         # rows a page, pages a pool, pages a lane
WIDTHS = (128, 256, 128)             # keys, values, a third pool's rows


def _pools(n, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(PAGES, PS, w), dtype) for w in WIDTHS[:n]]


def _rows(n, count, dtype, seed=1):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(count, w), dtype) for w in WIDTHS[:n]]


def _tables(lanes, trash=()):
    """Distinct pages a lane from 1 on; the ``trash`` lanes' rows all 0."""
    tables = 1 + np.arange(lanes * TABLE, dtype=np.int32).reshape(lanes, TABLE)
    tables[list(trash)] = 0
    return jnp.asarray(tables)


def _both(pools, rows, tables, wpos, keep):
    """The scatter's pools and the kernel's, page 0 (the trash page, which
    takes its rows in no defined order) left out."""
    max_len = TABLE * PS
    args = (rows, tables, jnp.asarray(wpos, jnp.int32), max_len)
    want = jax.jit(lambda p, k: paged_write.write_rows(p, *args, k))(
        pools, jnp.bool_(keep))
    got = jax.jit(lambda p, k: paged_write.write_rows_or_skip(p, *args, k))(
        pools, jnp.bool_(keep))
    return [np.asarray(x[1:], np.float32) for x in want], [
        np.asarray(x[1:], np.float32) for x in got]


@pytest.fixture
def kernels_on(monkeypatch):
    """The kernel, interpreted; the test fails if the call was not its."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    calls = []
    for name in ("write_a_row_a_lane", "write_a_span"):
        def counted(*args, _inner=getattr(writer, name)):
            calls.append(1)
            return _inner(*args)
        monkeypatch.setattr(writer, name, counted)
    yield calls
    assert calls, "the scatter ran, not the kernel"


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("n", [2, 3], ids=["two_pools", "three_pools"])
@pytest.mark.parametrize("keep", [True, False], ids=["kept", "dropped"])
@pytest.mark.parametrize("form", ["tick", "tick_of_20", "aligned", "mid_page",
                                  "to_the_end"])
def test_the_kernel_leaves_the_scatters_bits(kernels_on, form, keep, n, dtype):
    pools = _pools(n, dtype)
    if form.startswith("tick"):
        # a row a lane at its own offset; lanes 1 and 3 hold no page there
        # (the trash page), lane 2 is pinned past the cache's end
        lanes = 20 if form == "tick_of_20" else 5
        wpos = (np.arange(lanes) * 7 + 3) % (TABLE * PS)
        wpos[2] = TABLE * PS + 4
        tables, rows = _tables(lanes, trash=(1, 3)), _rows(n, lanes, dtype)
    else:
        # one lane's 3 pages of rows: from a page's first row, from the
        # middle of one (4 pages touched), up to the cache's last row
        wpos = [{"aligned": PS, "mid_page": PS + 5,
                 "to_the_end": (TABLE - 3) * PS}[form]]
        tables, rows = _tables(1), _rows(n, 3 * PS, dtype)
    want, got = _both(pools, rows, tables, wpos, keep)
    for before, w, g in zip(pools, want, got):
        np.testing.assert_array_equal(g, w)
        changed = (w != np.asarray(before[1:], np.float32)).any()
        assert changed == keep


def test_other_shapes_and_backends_keep_the_scatter(monkeypatch):
    """Rows that could share a page (a verify call's ``k + 1``, a bucket
    that is no whole number of pages), pools the kernel does not hold and a
    backend without the kernels take ``write_rows`` itself."""
    pools, tables = _pools(2, jnp.float32), _tables(2)
    called = []
    monkeypatch.setattr(writer, "_call", lambda *a: called.append(a))
    several = (_rows(2, 2 * 3, jnp.float32), tables, jnp.asarray([3, 9]))
    one = (_rows(2, 2, jnp.float32), tables, jnp.asarray([3, 9]))
    for env, args, kwargs in (("1", several, {}), ("0", one, {}),
                              ("1", one, {"kernel": False})):
        monkeypatch.setenv("FLEETX_FORCE_FLASH", env)
        got = paged_write.write_rows_or_skip(
            pools, *args, TABLE * PS, jnp.bool_(True), **kwargs)
        want = paged_write.write_rows(pools, *args, TABLE * PS,
                                      jnp.bool_(True))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    narrow = [p[..., :64] for p in pools]
    assert not writer.takes(narrow, [r[:, :64] for r in one[0]], 2)
    assert not writer.takes([p.astype(jnp.int8) for p in pools], one[0], 2)
    assert not writer.takes(pools, one[0], 1 << 20)
    assert writer.takes(pools, one[0], 2) and not called


@pytest.mark.parametrize("config,both", [
    ("jamba2-3b", True), ("lfm2-8b-a1b-l14", True), ("solar-open2-ep16-l8", True),
    ("axk1-ep16-l6", False), ("longcat-flash-ep32-l4", False),
    ("trinity-large-ep8-l5", False)])
def test_the_writer_is_chosen_by_the_plans_counts(monkeypatch, config, both):
    """A stack with an attention kind AND a recurrent kind hands its
    key/value write to ``write_rows_or_skip``, once a program's scanned
    body; a stack of one kind never does (its jaxprs are held to the
    parent's digests in ``tests/test_longcat_serving.py`` and
    ``tests/test_solar2_serving.py``)."""
    from tests.test_longcat_serving import traced_programs

    calls = []

    def counted(*args, _inner=paged_write.write_rows_or_skip, **kwargs):
        calls.append(kwargs)
        return _inner(*args, **kwargs)

    monkeypatch.setattr(paged_write, "write_rows_or_skip", counted)
    traced_programs(f"perfbench/configs/{config}.json")
    assert len(calls) == (2 if both else 0)


@pytest.mark.parametrize("config,fields", [
    ("jamba2-3b", (2, 26)), ("lfm2-8b-a1b-l14", (3, 11)),
    ("solar-open2-ep16-l8", (2, 6)), ("trinity-large-ep8-l5", (5, 0)),
    ("gpt-1.3b", None)])
def test_the_spans_constants_are_the_plans_counts(config, fields):
    """``kv_write_layers`` / ``state_layers`` of every ``serving.prefill``
    and ``serving.decode`` span: the layers whose key/value write lands and
    the layers whose recurrent state advances; none for a model without
    layer types."""
    from fleetx_tpu.models.gpt.mixed_stack import mover_layers
    from fleetx_tpu.models.gpt.model import GPTConfig
    from perfbench import harness

    cfg = GPTConfig.from_model_config(
        harness.load_json(f"perfbench/configs/{config}.json")["model"])
    assert mover_layers(cfg) == ({} if fields is None else dict(
        zip(("kv_write_layers", "state_layers"), fields)))
