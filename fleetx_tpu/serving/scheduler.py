"""Continuous-batching scheduler: admission queue + request records.

The policy seam of the serving stack. ``FIFOScheduler`` is deliberately
minimal — arrival order in, arrival order out — because admission policy
is the part operators replace first (priority tiers, per-tenant fairness,
SLA-aware preemption all slot in here without touching the engine): the
engine only asks "how deep is the queue" and "who is next".
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable, List, Optional

import jax
import numpy as np

__all__ = ["Request", "FIFOScheduler"]


@dataclasses.dataclass
class Request:
    """One in-flight serving request and its per-request decode knobs.

    ``rng_key`` is this request's OWN sampling stream (derived from the
    engine base key and the request id, or an explicit per-request seed),
    so repeated identical submissions sample independently. ``on_token``
    streams each decoded token as ``on_token(request_id, token, finished)``
    the tick it is produced.

    Lifecycle (``phase``): ``queued`` → [``prefilling``] → ``active`` →
    ``finished``. The ``prefilling`` state exists only under chunked
    prefill (``FLEETX_SERVING_PREFILL_CHUNK`` > 0, docs/SERVING.md): a
    long prompt's KV ingestion is spread over scheduler ticks — one
    chunk per tick, interleaved with the batched decode — with
    ``prefill_pos`` tracking how many prompt tokens (shared prefix
    included) have been written into the request's pages so far."""

    id: int
    prompt: np.ndarray  # [prompt_len] int32, no padding
    max_new_tokens: int
    min_new_tokens: int
    eos_token_id: int  # -1 disables EOS retirement
    greedy: bool
    temperature: float
    top_k: int  # 0 = no filter (engine normalizes >=vocab to 0)
    top_p: float
    rng_key: jax.Array
    on_token: Optional[Callable[[int, int, bool], None]] = None
    submit_time: float = 0.0
    # admission-control limits, resolved by the engine at submit (0 = off):
    # queue_ttl_s bounds time WAITING for a slot, deadline_s bounds the
    # whole submit->finish lifetime; both retire as finish_reason="timeout"
    queue_ttl_s: float = 0.0
    deadline_s: float = 0.0
    # filled in by the engine over the request's lifecycle
    slot: Optional[int] = None
    admit_time: Optional[float] = None
    first_token_time: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    # chunked-prefill lifecycle (class docstring): covered by the
    # engine's transactional-tick snapshot so a rolled-back tick
    # restores chunk progress exactly
    phase: str = "queued"
    prefill_pos: int = 0
    # speculative-decoding draft accounting (docs/SERVING.md): lifetime
    # proposed/accepted draft tokens for THIS request — also snapshot-
    # covered, so a tick that faults mid-verify rolls its counts back
    # with its tokens and recovery replay stays byte-identical
    spec_proposed: int = 0
    spec_accepted: int = 0
    # disaggregated serving (docs/SERVING.md): decoded page payloads a
    # PREFILL-role replica shipped for this prompt — consumed (and
    # cleared) by the engine's shipped-KV admission; a request whose
    # shipped admission rolled back re-admits through the replay seam
    kv_payloads: Any = dataclasses.field(default=None, repr=False)
    # rows that are not tokens and positions that are not rows (docs/
    # SERVING.md "Rows from a tower", "Positions apart from rows"), derived
    # by the engine at submit for a model that takes them: ``keys`` [prompt_len]
    # int64, what the prefix trie is keyed by (a text row's id; an image
    # row's hash, negative); ``positions`` [3, prompt_len] int32, the rotary
    # position of every prompt row on the three axes; ``rope_delta``, what a
    # decoded row's position stands past its cache row; ``images``, one
    # ``{"start", "grid", "patches"}`` a prompt image (its first row, its
    # patches' rows and columns, its uint8 patches in merge order) and
    # ``staged``, those of them whose rows the tower has written for the
    # admission under way; ``keyed``, the future of the worker thread that
    # hashes the images, after which ``keys`` is whole and the request may
    # be admitted (``patches`` is a future of that thread too, an image
    # each). None / empty for a request of token ids alone
    keys: Optional[np.ndarray] = dataclasses.field(default=None, repr=False)
    positions: Optional[np.ndarray] = dataclasses.field(default=None,
                                                        repr=False)
    rope_delta: int = 0
    images: list = dataclasses.field(default_factory=list, repr=False)
    staged: set = dataclasses.field(default_factory=set, repr=False)
    keyed: Any = dataclasses.field(default=None, repr=False)

    @property
    def prompt_len(self) -> int:
        """Number of real prompt tokens."""
        return int(self.prompt.shape[0])


class FIFOScheduler:
    """First-in-first-out admission queue over :class:`Request`."""

    def __init__(self):
        self._queue: collections.deque = collections.deque()

    def submit(self, request: Request) -> None:
        """Append a request to the tail of the admission queue."""
        self._queue.append(request)

    def pop_next(self) -> Optional[Request]:
        """Next request to admit (None when the queue is empty)."""
        return self._queue.popleft() if self._queue else None

    def requeue(self, request: Request) -> None:
        """Put a request back at the HEAD of the queue — the recovery
        path for a mid-prefill (chunked) request whose partial KV died
        with the device cache: it was the FIFO head when admitted and no
        token has been emitted, so restarting it from the front preserves
        both arrival order and byte-identity."""
        self._queue.appendleft(request)

    def peek(self) -> Optional[Request]:
        """Next request WITHOUT removing it — the page-granular admission
        path inspects the head's prompt (pages needed vs pages free) and
        only pops once admission is certain, so a too-big head blocks
        FIFO order instead of being silently dropped or reordered."""
        return self._queue[0] if self._queue else None

    def remove(self, request_id: int) -> Optional[Request]:
        """Pull one queued request out by id (None if not queued) — the
        cancel() path for requests that never won a slot."""
        for r in self._queue:
            if r.id == request_id:
                self._queue.remove(r)
                return r
        return None

    def snapshot(self) -> tuple:
        """Immutable view of the queue for the engine's transactional tick
        (crash-safe serving, docs/RESILIENCE.md): captured before device
        work, handed back to :meth:`restore` if the tick fails. Replacement
        schedulers must implement both so a rolled-back tick restores THEIR
        internal order too."""
        return tuple(self._queue)

    def restore(self, snap: tuple) -> None:
        """Reinstate a queue captured by :meth:`snapshot` (the requests
        themselves are restored field-by-field by the engine)."""
        self._queue = collections.deque(snap)

    def drain_all(self) -> List[Request]:
        """Remove and return every queued request (graceful-drain deadline:
        whatever never won a slot is retired with empty tokens)."""
        out = list(self._queue)
        self._queue.clear()
        return out

    def pop_expired(self, now: float) -> List[Request]:
        """Remove and return every queued request whose queue-TTL or total
        deadline has passed at ``now``. Arrival order is preserved for the
        survivors; a queue with no limits configured costs one scan."""
        if not any(r.queue_ttl_s or r.deadline_s for r in self._queue):
            return []
        dead, keep = [], collections.deque()
        for r in self._queue:
            waited = now - r.submit_time
            if ((r.queue_ttl_s and waited > r.queue_ttl_s)
                    or (r.deadline_s and waited > r.deadline_s)):
                dead.append(r)
            else:
                keep.append(r)
        self._queue = keep
        return dead

    def __len__(self) -> int:
        return len(self._queue)

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot."""
        return len(self._queue)

    def queued_tokens(self) -> int:
        """Prompt tokens waiting in the queue — the load signal that
        prices a PREFILL-role replica (prefill cost scales with tokens,
        not request count; docs/SERVING.md "Disaggregated
        prefill/decode"). The engine adds in-flight chunked-prefill
        remainders on top."""
        return sum(r.prompt_len for r in self._queue)
