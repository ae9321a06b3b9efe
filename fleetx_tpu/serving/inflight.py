"""The one decode tick a :class:`ServingEngine` keeps in flight.

``_tick_decode`` dispatches tick n and only then reads tick n-1 (engine.py
module docstring, "Tick order"). Between its dispatch and its collection a
tick is an :class:`InflightTick`: its two small outputs, still on the
device, and the ``{slot: request}`` map of its dispatch, which decides
whose each token is when it is read. Host code only: nothing here is
traced, so the compiled programs' cache keys (which hold the source lines of
every frame above a traced function) do not see it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from fleetx_tpu.serving.scheduler import Request

# Why a tick was read with no tick behind it on the device, one cause a
# tick: the speculative proposer reads the tokens on the host; an armed
# watchdog blocks on the program by design; a deadline eviction keeps the
# token in the partial result; a dry pool must know which lanes finished
# before it decides ``cache_full``; a step() found no lane left to dispatch
# for; cancel / export_kv / emitted_tokens / a snapshot of the device's
# counters / shutdown / recover act on (or report) exact state.
FLUSH_CAUSES = ("spec", "watchdog", "evict", "pool_dry", "idle", "other")


@dataclasses.dataclass
class InflightTick:
    """One dispatched, unread decode tick."""

    tok: object                 # device [slots] int32: the tick's tokens
    done: object                # device [slots] bool: lanes it finished
    lanes: Dict[int, Request]   # slot -> request, as dispatched
    program: int                # its dispatch's number (engine._next_program)


def pending_of(tick: Optional[InflightTick], slot: int, req: Request) -> int:
    """1 while ``req``'s token of ``tick`` (the tick in flight, or None) is
    unread in lane ``slot``, else 0."""
    return int(tick is not None and tick.lanes.get(slot) is req)
