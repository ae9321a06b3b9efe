"""What a :class:`ServingEngine` keeps in flight: one decode tick, and the
first tokens of the admissions of the step it is in.

``_tick_decode`` dispatches tick n and only then reads tick n-1 (engine.py
module docstring, "Tick order"). Between its dispatch and its collection a
tick is an :class:`InflightTick`: its two small outputs, still on the
device, and the ``{slot: request}`` map of its dispatch, which decides
whose each token is when it is read.

An admission's first token is kept the same way: the lane install takes it
on the device, right behind the prefill, and the host reads it only after
a LATER program has been dispatched (the next admission's prefill, or the
step's tick). Between the install and that read the admission is an
:class:`InflightFirstToken` in the engine's FIFO; none outlives the
``step()`` that made it. Host code only: nothing here is traced, so the
compiled programs' cache keys (which hold the source lines of every frame
above a traced function) do not see it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Optional

from fleetx_tpu.serving.scheduler import Request

# Why a tick, or a first token, was read with nothing dispatched behind it
# on the device, one cause a read: the speculative proposer reads the
# tokens on the host; an armed watchdog blocks on the program by design; a
# deadline eviction keeps the token in the partial result; a dry pool must
# know which lanes finished before it decides ``cache_full``; a step()
# found nothing left to dispatch; cancel / export_kv / emitted_tokens / a
# snapshot of the device's counters / shutdown / recover act on (or report)
# exact state; the engine's sample of an admission that holds its own wait
# (``engine._PROBE_PERIOD_S``). (A first token is never unread outside a
# step(), so only ``spec``, ``watchdog``, ``pool_dry``, ``idle`` and
# ``probe`` ever count one, and ``probe`` never counts a tick.)
FLUSH_CAUSES = ("spec", "watchdog", "evict", "pool_dry", "idle", "other",
                "probe")


@dataclasses.dataclass
class InflightTick:
    """One dispatched, unread decode tick."""

    tok: object                 # device [slots] int32: the tick's tokens
    done: object                # device [slots] bool: lanes it finished
    lanes: Dict[int, Request]   # slot -> request, as dispatched
    program: int                # its dispatch's number (engine._next_program)


@dataclasses.dataclass
class InflightFirstToken:
    """One admission whose lane is installed and whose first token is
    unread."""

    tok: object                 # device int32 scalar: the prefill's token
    req: Request
    program: int                # the prefill's dispatch number
    installed: int              # the lane install's dispatch number


def pending_of(tick: Optional[InflightTick],
               firsts: Iterable[InflightFirstToken], slot: int,
               req: Request) -> int:
    """The tokens of ``req`` in lane ``slot`` that are dispatched and
    unread: its token of ``tick`` (the tick in flight, or None) and its
    first token while it waits in ``firsts``."""
    return (int(tick is not None and tick.lanes.get(slot) is req)
            + sum(1 for first in firsts if first.req is req))
