"""Recurrent state in tail pages, on the pool alone (``PagePool`` is pure
host state; no model, no backend): a page's tail lives and dies with the
page. The state is modelled as what the pool's users do to it: one row a
physical page (``tails``), written by whoever prefills or decodes into the
page, read through the block table at the position just before a call's
first (``models/gpt/mixed_stack.py``)."""

import numpy as np
import pytest

from fleetx_tpu.serving.cache_manager import PagePool

PAGE = 4


class Lanes:
    """A pool with one tail a page; a request's state after ``t`` tokens is
    a function of those tokens alone (their running sum), so a wrong or a
    stale tail is seen at once."""

    def __init__(self, pages=13, lanes=3, lane_pages=6):
        self.pool = PagePool(pages, PAGE, lanes, lane_pages)
        self.tails = np.full(pages, np.nan)
        self.reads = []

    def state_before(self, lane, pos):
        """What a call at ``pos`` starts from: 0 at the start, else the
        tail of the page that holds ``pos - 1``."""
        if pos == 0:
            return 0.0
        page = int(self.pool.tables[lane, (pos - 1) // PAGE])
        assert page != 0, "a live lane read the trash page's tail"
        self.reads.append(page)
        return self.tails[page]

    def write(self, lane, tokens, start, state):
        for pos in range(start, len(tokens)):
            state = state + float(tokens[pos])
            self.tails[int(self.pool.tables[lane, pos // PAGE])] = state
        return state

    def admit(self, lane, tokens):
        """Alloc, prefill from the match's end, register: returns the
        tokens matched and the state at the prompt's end."""
        matched = self.pool.alloc(lane, tokens)
        assert matched is not None
        state = self.write(lane, tokens, matched,
                           self.state_before(lane, matched))
        self.pool.register_prefix(lane, tokens)
        return matched, state


def prompt(prefix, own, seed):
    rng = np.random.default_rng(seed)
    return np.concatenate([prefix, rng.integers(1, 99, own)]).astype(np.int64)


PREFIX = np.arange(1, 13)          # three full pages


@pytest.mark.parametrize("shared", [4, 8, 12])
def test_a_match_ending_at_any_page_boundary_finds_the_state_of_that_boundary(
        shared):
    held = Lanes()
    first = prompt(PREFIX, 5, 1)
    assert held.admit(0, first) == (0, float(first.sum()))
    second = prompt(PREFIX[:shared], 6, 2)
    matched, state = held.admit(1, second)
    assert matched == shared
    assert state == float(second.sum())      # resumed, not recomputed
    held.pool.check_invariants()


def test_a_parked_pages_state_is_revived_with_it_and_dies_with_its_eviction():
    held = Lanes(pages=9, lanes=2, lane_pages=6)
    first = prompt(PREFIX, 2, 1)
    held.admit(0, first)
    held.pool.free(0)                         # parks the registered pages
    assert held.pool.cached_pages == 3
    again = prompt(PREFIX, 3, 2)
    matched, state = held.admit(0, again)     # revived from the LRU park
    assert (matched, state) == (12, float(again.sum()))
    held.pool.free(0)
    # a prompt that needs every page evicts the parked subtree: its tails
    # are then overwritten by the new owner BEFORE anyone reads them
    other = prompt(np.arange(50, 70), 3, 3)
    held.reads.clear()
    matched, state = held.admit(1, other)
    assert (matched, state) == (0, float(other.sum()))
    assert held.pool.evicted == 3 and not held.reads
    held.pool.free(1)
    matched, state = held.admit(0, prompt(PREFIX, 2, 4))
    assert matched == 0                       # the snapshot died with the page


def test_retire_and_rebuild_leave_no_lane_with_another_requests_state():
    held = Lanes()
    a, b = prompt(PREFIX, 5, 1), prompt(np.arange(30, 38), 7, 2)
    held.admit(0, a)
    held.admit(1, b)
    held.pool.free(0)                                   # a retires
    c = prompt(np.arange(60, 68), 9, 3)                 # takes a's lane
    matched, state = held.admit(0, c)
    assert (matched, state) == (0, float(c.sum()))
    # lane 1 decodes on: its state is still its own
    assert held.state_before(1, len(b)) == float(b.sum())
    # recover(): a new pool, every request in flight replayed through it
    fresh = Lanes()
    for lane, tokens in ((0, c), (1, b)):
        assert fresh.admit(lane, tokens)[1] == float(tokens.sum())
    fresh.pool.check_invariants()
    held.pool.check_invariants()


def test_a_free_lanes_table_routes_to_the_trash_page_whose_tail_nobody_reads():
    held = Lanes()
    held.admit(0, prompt(PREFIX, 3, 1))
    held.pool.free(0)
    assert not held.pool.tables[0].any()      # every write goes to page 0
    held.tails[0] = 12345.0                   # whatever a free lane wrote
    matched, state = held.admit(0, prompt(PREFIX, 2, 5))
    assert matched == 12 and 0 not in held.reads
    assert state == float(prompt(PREFIX, 2, 5).sum())


def test_the_counters_count_pages_entered_matched_and_evicted():
    held = Lanes(pages=9, lanes=2, lane_pages=6)
    held.admit(0, prompt(PREFIX, 2, 1))
    assert (held.pool.registered, held.pool.matched,
            held.pool.matched_allocs, held.pool.evicted) == (3, 0, 0, 0)
    held.admit(1, prompt(PREFIX[:8], 3, 2))
    assert (held.pool.registered, held.pool.matched,
            held.pool.matched_allocs) == (3, 2, 1)
