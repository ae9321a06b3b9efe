"""The window class of pages without a model: ``WindowPagePool`` is pure
host state, like ``PagePool`` (``serving/cache_manager.py`` "Two classes of
page"). A lane is driven as the engine drives it (``prepare`` before every
prefill chunk and every decode token) and the allocator's promises are
checked at every step. The one-class behaviour of the accepted cells is
``PagePool``'s alone, which this PR leaves as it was: its tests
(tests/test_paged_serving.py and the chaos suite) run unchanged."""

import numpy as np
import pytest

from fleetx_tpu.serving.cache_manager import PagePool, WindowPagePool


def drive(pool, lane, prompt, new, chunk, seen=None):
    """Prefill ``prompt`` tokens in chunks and decode ``new``, as the engine
    calls the pool; ``seen(pos, n)`` after every call."""
    for at in range(0, prompt, chunk):
        n = min(chunk, prompt - at)
        assert pool.prepare(lane, at, n)
        if seen:
            seen(at, n)
    for pos in range(prompt, prompt + new):
        assert pool.prepare(lane, pos)
        if seen:
            seen(pos, 1)


@pytest.mark.parametrize("window,chunk,page,prompt", [
    (32, 16, 8, 100), (32, 16, 8, 13), (4096, 512, 16, 12288),
    (24, 8, 8, 77), (16, 16, 16, 64)])
def test_a_lane_holds_the_window_a_chunk_and_a_page_and_no_live_row_is_let_go(
        window, chunk, page, prompt):
    table = -(-(prompt + 40) // page)
    pool = WindowPagePool(
        num_pages=-(-(window + chunk) // page) + 2, page_size=page, lanes=1,
        table_pages=table, window=window, span=chunk)

    def seen(pos, n):
        held = int(pool.end[0] - pool.first[0])
        assert held * page <= window + chunk + page
        assert held <= pool.lane_pages
        # every key a query of this call sees, and every row it writes,
        # lies on a page the lane holds
        for j in range(max(pos - window + 1, 0), pos + n):
            assert pool.tables[0, j // page] != 0, (pos, n, j)
        pool.check_invariants()

    drive(pool, 0, prompt, 40, chunk, seen)
    released = max(prompt + 39 - window + 1, 0) // page
    assert pool.recycled == released
    assert not pool.tables[0, :released].any()
    pool.free(0)
    assert pool.pages_in_use == 0 and not pool.tables.any()
    pool.check_invariants()


def test_a_pool_of_lanes_times_lane_pages_never_runs_dry():
    rng = np.random.default_rng(0)
    lanes, window, chunk, page = 5, 32, 16, 8
    pool = WindowPagePool(lanes * 7 + 1, page, lanes, 40, window, chunk)
    assert pool.lane_pages == 7
    at = [0] * lanes
    for _ in range(600):
        lane = int(rng.integers(lanes))
        if at[lane] > 250 or (at[lane] and rng.random() < 0.02):
            pool.free(lane)          # retired, or its tick rolled back
            at[lane] = 0
            continue
        n = int(rng.integers(1, chunk + 1)) if at[lane] < 100 else 1
        assert pool.prepare(lane, at[lane], n)
        at[lane] += n
        pool.check_invariants()
    for lane in range(lanes):
        pool.free(lane)
    assert pool.pages_in_use == 0
    pool.check_invariants()


def test_a_dry_pool_refuses_and_a_retired_lane_gives_every_page_back():
    pool = WindowPagePool(8, 8, 2, 20, window=32, span=16)  # 7 usable
    assert pool.pages_needed(100) == 7 and pool.pages_needed(9) == 2
    drive(pool, 0, 48, 0, 16)
    assert pool.pages_in_use == 6           # the query at 32 still sees row 1
    assert pool.prepare(0, 48) and pool.pages_in_use == 5   # 0..16 let go
    assert not pool.can_admit(100) and pool.can_admit(9)
    assert not pool.prepare(1, 0, 40)       # 5 pages wanted, 2 free
    pool.check_invariants()
    pool.free(1)                            # what it got comes back
    assert pool.pages_in_use == 5
    pool.free(0)
    assert pool.pages_in_use == 0 and pool.can_admit(100)
    with pytest.raises(ValueError, match="window"):
        WindowPagePool(4, 8, 1, 20, window=32, span=16)
    assert not pool.prepare(0, 19 * 8, 16)  # past the table


def test_a_lane_admitted_beyond_the_window_starts_where_its_queries_see():
    pool = WindowPagePool(20, 8, 1, 64, window=32, span=16)
    assert pool.prepare(0, 300, 1)
    assert (int(pool.first[0]), int(pool.end[0])) == (33, 38)
    pool.check_invariants()


def test_the_full_class_is_the_page_pool_it_always_was():
    """No prefix trie (refused over window layers), grow on demand, free:
    the same calls the one-class engine makes."""
    pool = PagePool(10, 8, 2, 6, prefix_cache=False)
    assert pool.alloc(0, list(range(20))) == 0
    assert pool.pages_in_use == 3 and pool.ensure_page(0, 24)
    pool.free(0)
    assert pool.pages_in_use == 0
    pool.check_invariants()
