"""ServingEngine: continuous-batching decode over the paged kv-cache.

The runtime layer between "a stream of requests" and the single-step
decode functions exposed by ``models/gpt/generation.py``:

- **submit()** queues a request (FIFO) with per-request overrides for
  max/min length, EOS, sampling knobs, and an independent RNG stream.
- **step()** is one scheduler tick: admit queued requests into free slots
  (prefill-on-insert — each prompt is prefilled batch-1 into its slot's
  storage, its first token sampled in the same jitted call), then ONE
  jitted decode step over ALL slots is DISPATCHED, then the decode step
  before it is read: its tokens emitted, per-slot EOS / max-length
  retirement freeing slots for the next tick's admissions ("Tick order"
  below).
- **drain()** ticks until queue and slots are empty and returns the
  finished :class:`ServingResult` records.

Tick order (one decode tick in flight; ``serving/inflight.py`` holds the
record): everything a tick needs lives on the device (``active``,
``lengths``, ``last_tok``, ``decoded``, ``rng``; a lane that samples EOS
or reaches its budget clears its own ``active`` bit inside the program),
so tick n+1 needs nothing that tick n's tokens decide. ``_tick_decode``
therefore dispatches tick n and only THEN reads tick n-1
(``serving.fetch``, ``serving.emit``): the result's way back, the emit
loop, the metrics block after the tick and the caller's work between two
``step()`` calls all run while tick n is on the device. What follows from it:

- the host's position (``cache_manager.lengths``) is the position AS
  DISPATCHED: it advances by one for every lane a tick is dispatched for,
  and page growth, window-page recycling, the ``serving.decode`` span's
  row counts and the block tables are reckoned from it. A request's token
  list is what was DELIVERED, at most one token behind.
- a token goes to the request that still holds the lane it was dispatched
  for: not to one cancelled, expired or retired by its own callback in
  between, never to the lane's next tenant.
- a lane frees one tick late, and that is all that is late: a request
  that finished in tick n-1 is known finished after tick n's dispatch
  (tick n carries the lane inactive), and an admission takes the lane in
  the next ``step()``. The host counts ``max_new_tokens`` and cache-end
  finishes itself (only EOS needs the token), so it grows no page for,
  and dispatches no tick made only of, lanes whose last token is already
  in flight.
- ONE path that adapts, by what the engine already is: a speculative
  engine (the proposer reads the tokens on the host) and an engine with
  the watchdog armed (``tick_timeout_s`` > 0 blocks on the program by
  design) read every tick right after its dispatch, through the same
  function called earlier. Whatever must act on exact state reads the
  tick in flight first: ``cancel``, a deadline eviction (the partial
  result keeps the token), a dry pool before ``cache_full`` is decided,
  ``export_kv``, ``emitted_tokens``, a ``metrics.snapshot()`` that reads
  the device's counters, the end of the grace window, ``recover`` called
  from outside, and a ``step()`` that finds no lane left to dispatch for.
  With no tick in flight the host is where it always was between two
  steps: ``lengths[slot] == prompt_len + len(tokens) - 1`` for every
  active lane.
  ``metrics.snapshot()`` counts both ways: ``decode_ticks_overlapped``
  (dispatched while the tick before was unread) and
  ``decode_ticks_flushed`` (read with nothing behind it, by cause:
  ``_spec``, ``_watchdog``, ``_evict``, ``_pool_dry``, ``_idle``,
  ``_other``); ``serving.decode`` carries ``inflight=0|1`` and its
  ``program`` number, which the ``serving.fetch`` that reads the tick
  repeats as ``reads`` (a flushed one beside ``flushed=<cause>``).
- a device error of tick n surfaces at tick n+1's dispatch or at the
  read, one ``step()`` later, inside the same transaction: the rollback
  drops the unread tick, whose tokens never reached host truth, so replay
  recovery computes them again and nothing is emitted twice. The
  snapshot re-commits after every delivery, so tokens that were emitted
  stay emitted whatever fails later in the step.
- an admission's first token stays in flight the same way
  (``_dispatch_first_token`` / ``_read_first_tokens``): the lane install
  takes the prefill's token as the device scalar the program returned and
  decides the lane's ``active`` bit from it on the device, so the install
  is dispatched right behind the prefill and the host reads the token
  (``serving.first_token``: TTFT, the callback, finish / park / activate)
  only after a LATER program has been dispatched: the next admission's
  prefill in the same ``step()``, or the step's tick. The device's line
  reads prefill A, install A, prefill B, install B, ..., tick with no
  hole. Between install and read the admission is an entry of
  ``_first_tokens`` and, unless ``max_new_tokens`` <= 1, already in the
  active set, where the tick's dispatch counts its unread token as it
  counts the unread tick's (``pending_of``). NONE is unread when
  ``step()`` returns: what nothing was dispatched behind is read at the
  end of the step (``first_tokens_flushed_idle``), so whatever acts on
  exact state outside a step finds the host where it always was. An
  engine that reads every tick at once (``_sync_cause``) reads every first
  token right after its install, and so does, at most once every
  ``_PROBE_PERIOD_S``, the first admission of a step
  (``first_tokens_flushed_probe``): the sample of admissions whose
  ``serving.admit`` span holds its own wait, for readers of an admission's
  host time as the span less that wait. A first token that turns out to
  be EOS is learnt one dispatch late: the install left the lane inactive, the
  tick already dispatched carries the slot in its ``lanes`` map and its
  token for it is dropped like any other departed request's. A device
  error of prefill A surfaces at B's dispatch or at A's read, and is A's
  failed prefill (one strike for A); B, dispatched behind it and unread,
  is dropped with the rollback and re-admitted from the queue's head. The
  snapshot holds an admission whose token is unread as NOT yet admitted
  and re-bases after each read. ``metrics.snapshot()`` counts
  ``first_tokens_overlapped`` against ``first_tokens_flushed`` (by cause);
  ``serving.first_token`` carries ``overlapped=0|1``.

Cache storage is PAGED, the engine's one layout: K/V live in a shared
``[num_pages, page_size, heads*head_dim]`` pool, each request holds a
block table of page indices, and a refcounted prefix trie lets requests
sharing a token prefix (system prompts) reuse one prefill — admission is
page-granular (the queue head admits when its PAGES fit, not when a
worst-case slot does), prefill runs only over the non-shared prompt
suffix, and a request's chain grows page-by-page as it decodes
(``finish_reason="cache_full"`` when the pool runs dry mid-flight). See
``cache_manager.py`` for the allocator/trie and the no-zeroing safety
argument.

Chunked prefill (``FLEETX_SERVING_PREFILL_CHUNK``, default off;
docs/SERVING.md): whole-prompt prefill-on-insert makes decode TPOT
hostage to every long arriving prompt — prefill is MXU-bound, decode is
HBM-bound, and one 4k-token prefill inside a tick stalls every active
stream for its full duration. With a chunk size set, a prompt whose
non-shared suffix exceeds it enters a ``prefilling`` lifecycle state:
the engine runs AT MOST TWO chunk-sized prefill calls per tick (two
chunks of the prompt mid-prefill; or its last chunk and the next
request's admission; or two short prompts' whole-prompt calls:
``_step_inner`` runs the pass that takes one call twice), interleaved
with the batched decode, so no decode tick ever stalls more than ~two
chunks of prefill compute, and a prompt holds the admission head for half
the steps it did at one call a tick. The second call is a second CALL of
the same program: nothing is traced for it. Chunks reuse the bucketed
prefill jits at
chunk granularity — long prompts stop minting per-length buckets up to
``cache_len`` — writing through the same per-row ``cache_positions`` /
page-scatter seams decode uses: chunks write straight into the lane's
pages at absolute positions. The final chunk samples the first token
exactly where the one-call path would (same rng split discipline), so
greedy tokens are BYTE-IDENTICAL to the unchunked engine, and chunk
progress rides the transactional-tick snapshot: a mid-prefill fault
rolls back, recovery requeues the request at the queue head (zero
tokens emitted — byte-identity is structural) and the host-tier prefix
cache below makes the re-prefill cheap.
Deadlines are honored BETWEEN chunks: an expired request stops burning
prefill compute and retires ``finish_reason="timeout"`` with its lane
and pages freed (no partial-chunk leak — prefix registration only
happens at completion).

Host-DRAM KV spill tier (``FLEETX_SERVING_HOST_CACHE_BYTES``, default
off; docs/SERVING.md two-level page cache): when the paged pool would
LRU-evict a zero-ref warm trie page, the page (K/V + int8 scales) spills
to a bounded host store instead of being destroyed, keyed by its token-
chunk path; a later prompt with the same prefix revives it into fresh
physical pages via one batched transfer per cache leaf and skips that
prefill entirely — the millions-of-users shared-system-prompt scenario
where the hot prefix set exceeds HBM. The store is content-addressed and
engine-owned, so it SURVIVES replay recovery (the rebuilt pool matches
the same keys) and revived bytes are exactly the spilled bytes: cold vs
spill-revived decoding is byte-identical. A shared-disk tier stacks
under it (``FLEETX_SERVING_DISK_CACHE_DIR``/``_BYTES``): content-
addressed wire-format files every replica in the fleet revives from.

Phase-disaggregated serving (``role=`` kwarg / ``FLEETX_SERVING_ROLE``;
docs/SERVING.md "Disaggregated prefill/decode"): prefill is MXU-bound,
decode is HBM-bound — colocating them makes each the other's noisy
neighbor. A ``role="prefill"`` engine runs admission + (chunked)
prefill to completion, emits the first token, then PARKS the request
(``prefilled_ready()``) instead of decoding; ``export_kv(request_id)``
reads the ``ceil(prompt_len/page_size)`` pages covering the prompt out
of the pool (batched per-leaf gathers, int8 scales included) and
returns them as crc32-trailed wire-format blobs. A decode replica
admits them via ``submit(kv_payloads=..., history=[t0])``: pages are
allocated, shipped payloads written through the revive scatter (no
re-prefill), the prompt registered in its prefix trie, and decoding
resumes from ``t0`` with the RNG carry reconstructed — byte-identical
to colocated decoding. Any handoff failure (export fault, corrupt blob
caught by the crc at submit, replica death mid-ship) falls back to the
replay path: ``t0`` is already in the router's durable history, so
nothing is ever lost, only re-prefilled. ``role="decode"`` is a normal
engine the router labels for placement.

Per-slot progress is carried as explicit ``cache_positions`` into the
model (``SelfAttention._update_cache``), so slots decode at different
depths in one batched forward; each row's attention window is
``[0, lengths[slot]+1)`` — on TPU the flash-decode kernel receives that
window as its per-row ``end`` and streams only the live prefix. Inactive
slots ride the batched step with their writes pinned to the last cache
row and their outputs discarded; a freed slot's stale K/V is never
attended (see ``cache_manager.py``).

Quantized serving (docs/QUANTIZATION.md): ``FLEETX_SERVING_KV_DTYPE=int8``
stores decode K/V (the page pool) as int8 with per-vector fp32
scales — quantize-on-write in ``SelfAttention._update_cache``, dequant in
VMEM inside the flash-decode kernels — roughly halving the HBM bytes the
bandwidth-bound decode tick moves (and the pages a cached token pins).
``FLEETX_SERVING_WEIGHT_DTYPE=int8`` serves weight-only-PTQ params: the
tree is quantized once at construction (``ops/quant.quantize_tree_int8``)
and dequantized INSIDE the jitted prefill/decode calls, so XLA fuses the
scale multiply into each matmul consumer and HBM holds int8 + scales.
Replay recovery re-prefills through the same jitted seams, so crash
safety is precision-agnostic. Both knobs default off ("bf16" = the model
compute dtype), and the default path stays byte-identical; quantized
configs trade byte parity for a documented token/logit tolerance.
At ``"bf16"`` the layers' weights are RESIDENT in the compute dtype, the
attention's stacked input projections heads-major (docs/SERVING.md): what
every program would convert or re-lay out is made once at construction
(``executor.resident_params``). Same bits (tests/test_resident_weights.py).

Speculative decoding (``FLEETX_SERVING_SPEC=1``, default off;
docs/SERVING.md "Speculative decoding"): each tick a proposer
(serving/spec.py — n-gram prompt lookup by default, optionally a small
draft model) guesses up to ``FLEETX_SERVING_SPEC_K`` tokens per active
request, the drafts are written append-only into the request's pages,
and ONE batched prefill-shaped verification call — the same multi-token
``cache_positions`` seam replay/chunked prefill already write through —
scores all k+1 positions at once. Greedy acceptance keeps the longest
draft prefix matching the target argmax plus the correction token, so
greedy streams are BYTE-IDENTICAL to the non-speculative engine by
construction; sampling acceptance runs standard distribution-preserving
speculative rejection (accept d with prob p(d) for the deterministic
proposers, resample the rejection residual otherwise), consuming exactly
one rng split per EMITTED token so replay recovery's stream
reconstruction is unchanged. Rejected tails cost nothing: rollback is a
host-side pointer move (the per-row live length simply doesn't advance
past the accepted prefix — the no-zeroing live-window contract already
leaves stale K/V beyond the window unattended), and the engine clamps
each request's draft length to min(remaining token budget, page/lane
capacity) BEFORE proposing, so a k-token draft can never overrun
``max_length`` or its storage mid-verify. A verify-call fault rides the
same transactional-tick rollback + replay recovery as a plain decode
fault (per-request draft counters are snapshot-covered), and the
proposer's lane state resets with recovery and rebuilds lazily from
host truth.

Mesh-sharded serving (``mesh=`` kwarg; docs/SERVING.md "Mesh-sharded
serving"): the engine runs its device side over a TP/FSDP
``jax.sharding.Mesh`` (arXiv 2105.04663 GSPMD / 2204.06514 pjit are the
blueprint), so a model that does not fit — or does not hit latency
targets — on one chip serves from a mesh. What shards: params (and
quantized weight trees) get TP(mp)/FSDP shardings from the model's own
logical-axis metadata via ``parallel/sharding.serving_param_shardings``,
and the page pool (int8 scale leaves included) splits its heads axis
over ``mp`` — per-device cache bytes
and ``cache_nbytes()`` divide by the mp extent, which is the capacity
math a router prices replicas with. What replicates: the decode-lane
state dict, block tables, and every scalar. Every jitted device call
(bucketed prefill, chunk prefill, decode tick, spec verify, probe,
replay) runs under the mesh, and the flash-decode kernels run per-shard
inside ``shard_map`` over the local head slice (the PR 1 "meshes →
dense fallback" guard is lifted; ops/pallas/decode_attention.py), so
the live-prefix HBM-traffic contract holds per device. Host bookkeeping
— scheduler, lanes, trie, host spill tier, transactional snapshots,
replay recovery — is pure-host and MESH-AGNOSTIC: ``recover()``
rebuilds sharded device state from the same host truth, and greedy
streams are byte-identical to the single-device engine (the per-head
kernel math is unsharded math; the only reduction GSPMD splits is the
row-parallel output projection). pp/cp extents and head counts the mp
extent does not divide raise at construction.

Unsupported request shapes (beam search, repetition penalty, forced
EOS/BOS) raise at construction/submit — they need cross-step state the
slot loop does not carry; use the one-shot ``generate()`` for those.

Admission control & deadlines (docs/RESILIENCE.md): the queue is bounded
(``FLEETX_SERVING_MAX_QUEUE``, 0 = unbounded) and a full queue REJECTS
at submit with :class:`QueueFull` — explicit backpressure the caller can
act on, instead of unbounded growth under overload. Per-request
``queue_ttl_s`` (time waiting for a slot) and ``deadline_s`` (total
submit→finish lifetime) retire requests with ``finish_reason="timeout"``;
``cancel(request_id)`` frees a queued or in-flight request's slot
immediately. A raising ``on_token`` callback retires only ITS request
(``finish_reason="error"``) — neighbors' token streams are untouched.
With no limits configured every knob is inert and token outputs are
byte-identical to the unlimited engine.

Crash safety (docs/RESILIENCE.md serving-recovery):

- **Transactional ticks** — ``step()`` snapshots the pure-host
  bookkeeping (scheduler queue, request table, active map, results)
  before any device work (again after each admission and each delivery
  of a tick's tokens) and rolls it back on ANY exception, dropping the
  tick in flight, so a failed tick never loses or duplicates a token, a
  request, or a queue position.
- **Replay recovery** — device caches are pure functions of each
  request's ``prompt + emitted tokens``, so :meth:`ServingEngine.recover`
  rebuilds a fresh cache/pool/lane-table and re-prefills every active
  request's full history (the prefix trie makes shared prompts cheap),
  resuming byte-identically after a rolled-back tick or an external
  device reset. Bounded by ``FLEETX_SERVING_MAX_RECOVERIES`` consecutive
  recoveries without a productive tick → :class:`RecoveryExhausted`.
- **Poison quarantine** — a decode tick that fails again right after a
  recovery triggers bisection probing over the active set (non-donating
  probe ticks whose outputs are discarded) to isolate the request whose
  presence kills the batch; it is retired ``finish_reason="error"`` with
  its partial tokens and every neighbor continues byte-identically. A
  prefill that fails twice for the same request retires that request
  directly — no bisection needed, the culprit is known.
- **Watchdog** — with ``FLEETX_SERVING_TICK_TIMEOUT_S`` > 0 device calls
  run on a monitor-thread executor; a tick exceeding the timeout banks
  diagnostics in ``engine.hang_diagnostics`` and raises
  :class:`TickTimeout` into the same rollback→recovery path (the hung
  call is abandoned; recovery rebuilds fresh buffers).
- **Graceful drain** — :meth:`shutdown` (or SIGTERM via
  :meth:`install_sigterm_handler` → :meth:`request_shutdown`) stops
  admission (:class:`ShuttingDown` rejects at submit), keeps ticking so
  in-flight AND queued work finishes inside the grace window, then
  retires whatever remains with partial tokens and
  ``finish_reason="shutdown"`` — the hook a multi-replica router needs
  to rotate a replica out without dropping a byte.
- **Admit-with-history** — ``submit(history=...)`` aims the replay seam
  at a request ANOTHER replica started: the pre-emitted tokens replay
  through the same one-call prefill recovery uses, the RNG position
  reconstructs, and decoding continues from the last delivered token
  without re-firing its callbacks — the zero-token-loss failover
  primitive of the multi-replica router (serving/router.py). The
  :meth:`health`/:meth:`take_result`/:meth:`emitted_tokens`/
  :meth:`declare_dead` quartet is the rest of the router-facing
  surface.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import time
import weakref
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from fleetx_tpu.obs import http as obs_http
from fleetx_tpu.obs.events import emit as obs_emit
from fleetx_tpu.obs.tracing import span
from fleetx_tpu.models.gpt import paged_write
from fleetx_tpu.models.gpt.generation import (
    GenerationConfig,
    _top_p_cutoff_bisect,
)
from fleetx_tpu.serving.model_protocol import GPTExecutor, device_counters_of
from fleetx_tpu.serving.cache_manager import (
    KV_LEAF_RANK,
    DiskPageStore,
    HostPageStore,
    PagedKVCacheManager,
    TieredPageStore,
    window_lane_pages,
)
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.serving.inflight import (
    InflightFirstToken,
    InflightTick,
    pending_of,
)
from fleetx_tpu.serving.metrics import WAITS, ServingMetrics
from fleetx_tpu.serving.scheduler import FIFOScheduler, Request
from fleetx_tpu.serving.spec import build_proposer
from fleetx_tpu.utils.log import logger

__all__ = [
    "QueueFull",
    "RecoveryExhausted",
    "ServingEngine",
    "ServingResult",
    "ShuttingDown",
    "TickTimeout",
    "filter_logits",
    "sample_tokens",
]

_NEG = -1e9


class QueueFull(RuntimeError):
    """Admission refused: the queue is at ``FLEETX_SERVING_MAX_QUEUE``.
    The explicit backpressure signal — callers shed load or retry later;
    the engine never buffers unboundedly under overload."""


class ShuttingDown(RuntimeError):
    """Admission refused: the engine is draining toward shutdown
    (``QueueFull``-style explicit reject — a router in front of N
    replicas routes around a draining one instead of queueing into it)."""


class TickTimeout(RuntimeError):
    """A device tick exceeded ``FLEETX_SERVING_TICK_TIMEOUT_S``. Raised by
    the watchdog into the transactional-tick rollback, which then runs the
    recovery path; diagnostics are banked in ``engine.hang_diagnostics``."""


class RecoveryExhausted(RuntimeError):
    """More than ``FLEETX_SERVING_MAX_RECOVERIES`` consecutive recoveries
    without a productive tick: the fault is not request-shaped (quarantine
    would have cleared it), so the engine declares itself dead rather than
    spin forever — the caller restarts the process/device."""


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


# How often an admission's first token is read AT ONCE, inside its own
# ``serving.admit`` span as before PR 55 (``first_tokens_flushed_probe``):
# with first tokens in flight no admission's span holds its own wait, and
# a reader that takes an admission's host time as the span less that wait
# (perfbench's ``admit_host_ms_p50``) needs some that do. Each one costs the
# chip the 3-4 ms of idle the others save: at most 0.8% of the wall clock.
_PROBE_PERIOD_S = 0.5

# What a step carried, which its ``serving.tick`` says as it closes: the
# admissions, chunks and tower programs it ran, the tokens it delivered,
# and the prompt rows that went through its prefill programs (no padding).
_CARRIED = ("admitted", "chunked", "tower", "decoded", "prefill_rows")


# An admission's operands cross to the device PACKED, one host-built
# numpy array a dtype (PERF.md, PR 32): a prefill call takes the int32
# vector of _prefill_ints, a lane install the one of _install_lane, and
# temperature and top_p ride a float32 pair that one upload serves both
# with. Flags are 0/1 and become bool by comparison inside the program.


def _upload(at, host: np.ndarray):
    """THE host-to-device transfer of an admission: a plain copy of one
    numpy array (no device program, where ``jnp.asarray(scalar, dtype)``
    runs a convert), counted as ``transfers`` on the span whose attrs
    are ``at``."""
    at["transfers"] += 1
    return jax.device_put(host)


def _sampler_floats(req: Request) -> np.ndarray:
    """The float32 operand of a request's prefill and lane install."""
    return np.asarray([req.temperature, req.top_p], np.float32)


def _deactivate(st, slot):
    # clear one slot's active lane; its row still rides the batched decode
    # step (outputs discarded) exactly like any other free slot
    return {**st, "active": st["active"].at[slot].set(False)}


def filter_logits(logits, temperature, top_k, top_p, *, topk_cap: int):
    """THE per-row sampling filter pipeline — temperature scale, top-k
    via ONE static ``lax.top_k(topk_cap)`` partial sort (the per-row
    cutoff is the row's k-th entry; ``top_k`` pre-normalized to
    ``[0, topk_cap]``, 0 = no filter), then the sort-free top-p
    threshold bisection from ``generation.py`` with per-row targets.
    ``logits`` [n, vocab] with per-row knobs [n] → filtered logits
    (removed entries at ``_NEG``). Shared by :func:`sample_tokens` and
    the speculative ``_verify_fn`` so the two sampling paths can never
    drift apart."""
    vocab = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    cap = max(1, min(topk_cap, vocab))
    vals = jax.lax.top_k(scaled, cap)[0]  # [n, cap] descending
    kth = jnp.take_along_axis(
        vals, jnp.clip(top_k - 1, 0, cap - 1)[:, None], axis=-1
    )
    filtered = jnp.where((top_k > 0)[:, None] & (scaled < kth), _NEG, scaled)
    probs, thresh = _top_p_cutoff_bisect(filtered, top_p[:, None])
    return jnp.where(probs >= thresh, filtered, _NEG)


def sample_tokens(logits, keys, greedy, temperature, top_k, top_p, *,
                  topk_cap: int):
    """Vectorized per-row sampler: each batch row applies ITS OWN decode
    strategy (greedy flag, temperature, top-k, top-p) and draws from its
    own rng key — the per-request-overrides core of the serving engine.
    Filtering is :func:`filter_logits`; greedy rows take the argmax of
    the unfiltered logits (exactly ``_sample``'s greedy branch, so
    greedy parity with ``generate()`` holds per row)."""
    greedy_tok = jnp.argmax(logits, axis=-1)
    filtered = filter_logits(logits, temperature, top_k, top_p,
                             topk_cap=topk_cap)
    sampled = jax.vmap(jax.random.categorical)(keys, filtered)
    return jnp.where(greedy, greedy_tok, sampled).astype(jnp.int32)


@dataclasses.dataclass
class ServingResult:
    """Final outcome of one request: generated tokens + latency stats."""

    id: int
    prompt: np.ndarray
    tokens: np.ndarray  # generated tokens (EOS included when hit)
    # eos | max_length | cache_full | timeout | cancelled | error | shutdown
    # ("error" covers raising callbacks AND quarantined poison requests;
    # "shutdown" = graceful-drain grace window closed, partial tokens kept)
    finish_reason: str
    ttft_s: float
    latency_s: float

    @property
    def sequence(self) -> np.ndarray:
        """prompt + generated tokens, the one-shot ``generate()`` layout
        minus the post-EOS pad fill."""
        return np.concatenate([self.prompt, self.tokens])


class ServingEngine:
    """Continuous-batching serving loop over decode lanes and a page
    pool (module docstring)."""

    # construction is one span (docs/OBSERVABILITY.md "Start-up"): what it
    # traces, compiles or loads lies inside it as jit.* spans that name it
    # as their parent
    @span("serving.build")
    def __init__(self, model, variables, *, slots: Optional[int] = None,
                 cache_len: Optional[int] = None,
                 gen_cfg: Optional[GenerationConfig] = None,
                 base_seed: int = 0, topk_cap: Optional[int] = None,
                 prefill_bucket: Optional[int] = None,
                 log_every: Optional[int] = None,
                 metrics: Optional[ServingMetrics] = None,
                 max_queue: Optional[int] = None,
                 queue_ttl_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 paged: Optional[bool] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 max_recoveries: Optional[int] = None,
                 tick_timeout_s: Optional[float] = None,
                 grace_s: Optional[float] = None,
                 kv_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 prefill_chunk: Optional[int] = None,
                 host_cache_bytes: Optional[int] = None,
                 spec: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 spec_proposer=None,
                 role: Optional[str] = None,
                 disk_cache_dir: Optional[str] = None,
                 disk_cache_bytes: Optional[int] = None,
                 mesh=None, executor=None):
        gen_cfg = gen_cfg or GenerationConfig(decode_strategy="greedy")
        # the model-side serving contract (serving/model_protocol.py):
        # every model compute call below goes through the executor, and
        # the capability flags gate which engine features are legal —
        # the GPT executor is pure delegation to the pre-extraction
        # functions, so this engine's behavior is byte-identical. A
        # default executor is built LATER, over the decode-configured
        # model clone (cache length/pages ride cfg) — here only the
        # capability gates run.
        self.executor = executor
        self.capabilities = (executor.capabilities if executor is not None
                             else GPTExecutor(model).capabilities)
        self.model_family = self.capabilities.family
        self.capabilities.require(has_kv_cache=True)
        if gen_cfg.repetition_penalty != 1.0:
            raise ValueError("continuous batching does not support "
                             "repetition_penalty (use one-shot generate())")
        if gen_cfg.forced_eos_token_id is not None:
            raise ValueError("continuous batching does not support "
                             "forced_eos_token_id")
        self.gen_cfg = gen_cfg
        # mesh-native serving (module docstring "Mesh-sharded serving"):
        # params shard TP(mp)/FSDP, caches shard heads-over-mp, host
        # bookkeeping stays mesh-agnostic. Validated up front — an
        # unshardable config must fail here with a cause, not deep
        # inside the first traced model.apply.
        self.mesh = mesh
        self._rules = None
        if mesh is not None:
            from fleetx_tpu.parallel.sharding import make_rules

            shape = dict(mesh.shape)
            if shape.get("pp", 1) > 1 or shape.get("cp", 1) > 1:
                raise ValueError(
                    f"serving mesh {shape} has pp/cp extents; the decode "
                    "tick runs the full layer stack per device — use a "
                    "(dp, fsdp, mp) mesh")
            if model.cfg.num_attention_heads % shape.get("mp", 1):
                raise ValueError(
                    f"num_attention_heads {model.cfg.num_attention_heads} "
                    f"does not divide over mp={shape.get('mp', 1)}; the "
                    "kv cache shards over heads (module docstring)")
            if shape.get("dp", 1) > 1:
                # the engine shards nothing over dp (mp splits heads,
                # fsdp splits params): a dp extent just replicates the
                # decode tick on every dp device. Allowed — one engine
                # can own a predict()-shaped mesh — but the hardware
                # would serve more traffic as dp separate REPLICAS.
                logger.warning(
                    "serving: mesh has dp=%d — the decode tick is "
                    "REPLICATED over the dp axis (no throughput gain); "
                    "prefer %d independent engine replicas behind a "
                    "router", shape["dp"], shape["dp"])
            self._rules = make_rules(fsdp_params=shape.get("fsdp", 1) > 1)
        self.slots = slots or _env_int("FLEETX_SERVING_SLOTS", 8)
        # `paged` stays a keyword because perfbench/serving.py passes
        # paged=True (ROADMAP D2: a `benchmark` PR drops it there, then
        # the keyword goes); the slot layout it once chose was deleted at
        # PR 29, and input from outside the program is refused, not ignored
        if paged is not None and not paged:
            raise ValueError(
                "paged=False: the fixed per-slot cache layout was removed "
                "at PR 29 (docs/MIGRATION.md); the page pool is the "
                "engine's one layout — drop the argument")
        # an attribute, not a switch: the router and replica server tell
        # an engine with a page pool from a KV-free BatchingEngine
        # (batch_engine.py, False) by it
        self.paged = True
        self.page_size = page_size or _env_int("FLEETX_SERVING_PAGE_SIZE", 16)
        # phase-disaggregated serving (docs/SERVING.md "Disaggregated
        # prefill/decode"): a PREFILL-role replica runs admission and
        # (chunked) prefill to completion, then PARKS the request for
        # export_kv() instead of decoding; a DECODE-role replica is a
        # normal engine whose router feeds it shipped KV. "both" — the
        # default — is the colocated engine, byte-identical to before.
        self.role = (role or os.environ.get("FLEETX_SERVING_ROLE", "")
                     or "both")
        if self.role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'both', got "
                f"{self.role!r}")
        cache_len = (cache_len
                     or _env_int("FLEETX_SERVING_CACHE_LEN", 0)
                     or model.cfg.max_position_embeddings)
        # per-request logical capacity rounds to whole pages (the page
        # is also the flash-decode DMA tile)
        cache_len += -cache_len % self.page_size
        self.cache_len = cache_len
        # quantized serving (module docstring): kv int8 halves decode HBM
        # traffic + pages per cached token; weight int8 halves/quarters
        # servable-param HBM. "bf16" = the model's native compute dtype.
        from fleetx_tpu.ops.quant import resolve_serving_dtype

        self.kv_dtype = resolve_serving_dtype(
            kv_dtype, "FLEETX_SERVING_KV_DTYPE")
        self.weight_dtype = resolve_serving_dtype(
            weight_dtype, "FLEETX_SERVING_WEIGHT_DTYPE")
        self.capabilities.require(
            supports_int8_kv=self.kv_dtype == "int8",
            supports_int8_weights=self.weight_dtype == "int8",
            supports_mesh=mesh is not None,
            supports_roles=self.role != "both")
        # a model with recurrent state beside keys and values tells its
        # cached forward which rows of a call are tokens (``_row_mask``);
        # state held once a lane (cache_manager.py "Kinds of state") is
        # nothing a matched prefix could resume
        self._state_rows = self.capabilities.state_kinds != ("kv",)
        self._lane_state = getattr(model.cfg, "lane_state",
                                   ("", ()))[0]  # the kind's name, or ""
        if self._lane_state:
            self.capabilities.require(
                supports_prefix_cache=bool(prefix_cache))
            prefix_cache = False
        decode_kv = "int8" if self.kv_dtype == "int8" else None
        # default pool = every lane's full capacity in pages + the
        # reserved trash page; short requests then leave pages free for
        # extra concurrent tenants instead of padding dead lanes
        self.num_pages = (num_pages
                          or _env_int("FLEETX_SERVING_PAGES", 0)
                          or self.slots * (cache_len // self.page_size) + 1)
        # chunked prefill (module docstring): 0/off = today's whole-prompt
        # prefill-on-insert, byte-identical; >0 bounds per-tick prefill
        # work to one chunk-sized call so decode TPOT never stalls longer
        self.prefill_chunk = (prefill_chunk if prefill_chunk is not None
                              else _env_int("FLEETX_SERVING_PREFILL_CHUNK", 0))
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {self.prefill_chunk}")
        # a second class of page for a model's window-attention layers
        # (cache_manager.py "Two classes of page"): what the family cannot
        # ride is refused here, with its cause
        self.window_pages = None
        extra = {}
        # EVA's two classes ("summary" beside "window": cache_manager.py)
        self._eva = "summary" in self.capabilities.page_classes
        if "window" in self.capabilities.page_classes:
            self.capabilities.require(
                supports_prefix_cache=bool(prefix_cache),
                supports_roles=self.role != "both")
            prefix_cache = False
            if not self.prefill_chunk:
                raise ValueError(
                    f"model family {self.model_family!r} has window-attention "
                    "layers: prefill_chunk must be set (a lane's window pages "
                    "hold the window plus one chunk, and a whole-prompt "
                    "prefill through the dense path does not fit long "
                    "prompts)")
            if self._eva:
                self.num_pages = self._eva_pages(model.cfg, num_pages,
                                                 prefill_bucket)
            # sized so that no lane can meet a dry window class
            self.window_pages = self.slots * (
                model.cfg.eva_window_size // self.page_size if self._eva
                else window_lane_pages(
                    model.cfg.sliding_window, self.prefill_chunk,
                    self.page_size)) + 1
            extra["decode_window_pages"] = self.window_pages
        self.prefix_cache = (
            prefix_cache if prefix_cache is not None
            else _env_int("FLEETX_SERVING_PREFIX_CACHE", 1) == 1)
        self.model = model.clone(cfg=dataclasses.replace(
            model.cfg, decode_cache_len=cache_len,
            decode_num_pages=self.num_pages,
            decode_page_size=self.page_size,
            decode_kv_dtype=decode_kv, **extra))
        if self.executor is None:
            # wrap the decode-configured clone: init_cache/forward read
            # decode_cache_len/pages off cfg, so the executor must see
            # the same model object every pre-extraction call site saw
            self.executor = GPTExecutor(self.model,
                                        family=self.model_family)
        elif hasattr(self.executor, "bind"):
            self.executor = self.executor.bind(self.model)
        self.params = (variables["params"]
                       if isinstance(variables, dict) and "params" in variables
                       else variables)
        # the servable tree, once, up front. int8: weight-only PTQ, params
        # live in HBM as int8 + per-channel scales and every jitted prefill/
        # decode call dequantizes INSIDE the jit (_dequant_params), so XLA
        # fuses the scale multiply into the matmul consumers; idempotent for
        # pre-quantized trees (InferenceEngine). bf16: the executor converts
        # what the model would convert in every program (module docstring).
        from fleetx_tpu.ops.quant import serving_weight_params

        self.params = self.executor.resident_params(
            serving_weight_params(self.params, self.weight_dtype))
        if self.mesh is not None:
            # TP(mp)/FSDP-shard the (possibly quantized) servable tree:
            # committed NamedSharding inputs drive GSPMD inside every jit
            # from here on, no per-call annotations needed
            self.params = self._shard_params(self.params)
        self.topk_cap = topk_cap or _env_int("FLEETX_SERVING_TOPK_CAP", 64)
        self.prefill_bucket = (prefill_bucket
                               or _env_int("FLEETX_SERVING_PREFILL_BUCKET", 32))
        # host-DRAM KV spill tier (module docstring): 0/off = LRU eviction
        # destroys warm trie pages (today's behavior); >0 bounds the
        # pinned-host store warm pages spill into instead
        host_bytes = (host_cache_bytes if host_cache_bytes is not None
                      else _env_int("FLEETX_SERVING_HOST_CACHE_BYTES", 0))
        # cluster page tier (docs/SERVING.md "Disaggregated prefill/
        # decode"): a shared-directory, byte-bounded, content-addressed
        # disk store every replica points at — the prefix set one
        # replica's DRAM budget would miss stays warm fleet-wide. With
        # both tiers configured, TieredPageStore write-throughs puts and
        # promotes disk hits back into DRAM.
        disk_dir = (disk_cache_dir if disk_cache_dir is not None
                    else os.environ.get("FLEETX_SERVING_DISK_CACHE_DIR", ""))
        disk_bytes = (disk_cache_bytes if disk_cache_bytes is not None
                      else _env_int("FLEETX_SERVING_DISK_CACHE_BYTES", 0))
        self.capabilities.require(supports_host_spill=bool(
            host_bytes > 0 or (disk_dir and disk_bytes > 0)))
        tiered = self.prefix_cache
        dram = HostPageStore(host_bytes) if host_bytes > 0 and tiered else None
        self._disk_store = (DiskPageStore(disk_dir, disk_bytes)
                            if disk_dir and disk_bytes > 0 and tiered
                            else None)
        self._dram_store = dram
        self._host_store = (
            TieredPageStore(dram, self._disk_store)
            if dram is not None and self._disk_store is not None
            else dram if dram is not None else self._disk_store)
        self.log_every = (log_every if log_every is not None
                          else _env_int("FLEETX_SERVING_LOG_EVERY", 0))
        # admission control (module docstring): all default OFF — an
        # engine with no limits configured behaves byte-identically to the
        # pre-resilience engine
        self.max_queue = (max_queue if max_queue is not None
                          else _env_int("FLEETX_SERVING_MAX_QUEUE", 0))
        self.queue_ttl_s = (queue_ttl_s if queue_ttl_s is not None
                            else _env_float("FLEETX_SERVING_QUEUE_TTL_S", 0.0))
        self.deadline_s = (deadline_s if deadline_s is not None
                           else _env_float("FLEETX_SERVING_DEADLINE_S", 0.0))
        # crash safety (module docstring): recovery budget, hung-tick
        # watchdog, graceful-drain grace window
        self.max_recoveries = (
            max_recoveries if max_recoveries is not None
            else _env_int("FLEETX_SERVING_MAX_RECOVERIES", 8))
        self.tick_timeout_s = (
            tick_timeout_s if tick_timeout_s is not None
            else _env_float("FLEETX_SERVING_TICK_TIMEOUT_S", 0.0))
        self.grace_s = (grace_s if grace_s is not None
                        else _env_float("FLEETX_SERVING_GRACE_S", 30.0))
        self._recoveries_consecutive = 0
        self._tick_strikes = 0              # consecutive failed decode ticks
        self._prefill_strikes: Dict[int, int] = {}  # request id -> failures
        self._fault_ctx = None              # ("prefill", rid) during prefill
        self._fault_ticks = 0               # attempted decode device calls
        self._fault_prefills = 0            # attempted prefill device calls
        self._fault_ships = 0               # attempted KV exports
        self._watchdog = None               # lazy single-thread executor
        self.hang_diagnostics = None        # banked by the watchdog
        self._shutting_down = False
        self._dead = False  # RecoveryExhausted was raised; healthz -> 503
        self._shutdown_deadline = None
        self._shutdown_event_pending = False
        self._prev_sigterm = None
        self._now = time.perf_counter  # swappable clock (chaos tests)
        self.cache_manager = PagedKVCacheManager(
            self.model, self.slots, cache_len, self.num_pages,
            self.page_size, prefix_cache=self.prefix_cache,
            host_store=self._host_store, window_span=self.prefill_chunk)
        # mesh: the freshly-built cache tree splits its heads over mp
        # (scale leaves ride the same rule); state/tables replicate
        self.cache_manager.cache = self._shard_cache(self.cache_manager.cache)
        self._tables_dev = None       # device mirror of the block tables,
        self._tables_version = -1     # refreshed when the manager's moves
        self.scheduler = FIFOScheduler()
        self.metrics = metrics or ServingMetrics(self.slots)
        self.metrics.set_role(self.role)
        self._publish_quant_metrics()
        # what the model's programs counted on the device (an expert
        # model's routing): fetched by snapshot() alone, never by a tick
        self.metrics.device_counters = device_counters_of(self)
        self._base_key = jax.random.PRNGKey(base_seed)
        self._next_id = 0
        self._ticks = 0
        self._active: Dict[int, Request] = {}  # slot -> request
        # chunked prefill: slot -> the request mid-prefill there (at most
        # one by policy — the FIFO head — a dict for snapshot symmetry)
        self._prefilling: Dict[int, Request] = {}
        # disaggregated prefill: slot -> request whose prompt KV is fully
        # written on this PREFILL-role replica, parked (lane + pages held,
        # decode lane inert) until the router calls export_kv()
        self._prefilled: Dict[int, Request] = {}
        self._results: Dict[int, ServingResult] = {}
        # the decode tick dispatched and not yet read (module docstring
        # "Tick order"), and the lanes whose tokens this step() delivered
        self._inflight: Optional[InflightTick] = None
        self._delivered = 0
        # admissions whose lane is installed and whose first token is
        # unread, oldest first; empty whenever step() has returned
        self._first_tokens: collections.deque = collections.deque()
        # the step's own account: what refused the head of the queue in
        # this step, and what the step's prefill slot has carried so far
        self._refused: Optional[str] = None
        self._carried = {"tower": 0, "prefill_rows": 0}
        self._probed_at = self._now()  # the last admission read at once
        # programs dispatched so far (_next_program)
        self._programs = 0
        # positions apart from rows, rows from a tower (serving/rows_in.py):
        # a model without the flags sees neither operand, and its programs
        # are the ones they were
        self._mrope = self.capabilities.mrope
        self._state = self._replicate(self._init_state())
        self._kernel_steps = self._decode_kernel_steps()
        # buffer donation halves cache HBM residency on TPU; skipped on
        # CPU/interpret runs where XLA would only warn about it
        donate = jax.default_backend() == "tpu"
        self._donate_cache = donate
        self._tower = None
        if self.capabilities.takes_rows:
            from fleetx_tpu.serving.rows_in import Tower

            self._tower = Tower(self)
        # all_greedy is static: an all-greedy tick (the common serving mix
        # for deterministic decode) skips the sampler entirely — at most
        # two cached compilations
        self._decode_jit = jax.jit(
            self._decode_fn, static_argnums=(4,),
            donate_argnums=(1, 2) if donate else ())
        # bisection probes: NO donation — a probe's discarded outputs must
        # leave the committed cache/state buffers untouched
        self._probe_jit = jax.jit(self._decode_fn, static_argnums=(4,))
        self._admit_jit = jax.jit(self._admit_fn, donate_argnums=())
        # the install's token operand where the host packs the token itself
        # (a replay, a shipped admission): resident, never read
        self._no_token = jax.device_put(np.zeros((), np.int32))
        # a replay call's temperature and top_p: inert, and resident
        self._inert_floats = jax.device_put(np.ones(2, np.float32))
        self._deactivate_jit = jax.jit(_deactivate)
        self._prefill_jits = {}  # bucket_len -> jitted prefill
        # speculative decoding (module docstring): default OFF — a spec-
        # disabled engine never touches the proposer/verify machinery and
        # stays byte-identical to the pre-spec engine. An explicit
        # spec_proposer IMPLIES speculation (the kwarg wins over the
        # env); handing one to an explicitly spec=False engine is a
        # config contradiction, not something to ignore silently.
        self.spec = (spec if spec is not None
                     else True if spec_proposer is not None
                     else _env_int("FLEETX_SERVING_SPEC", 0) == 1)
        if spec_proposer is not None and not self.spec:
            raise ValueError(
                "spec_proposer was given but speculation is explicitly "
                "disabled (spec=False); drop one or the other")
        self.spec_k = (spec_k if spec_k is not None
                       else _env_int("FLEETX_SERVING_SPEC_K", 4))
        self._proposer = None
        self.capabilities.require(supports_spec=self.spec)
        if self.spec:
            if self.spec_k < 1:
                raise ValueError(
                    f"spec_k must be >= 1 when speculation is on, got "
                    f"{self.spec_k} (FLEETX_SERVING_SPEC_K)")
            self._proposer = spec_proposer or build_proposer(
                os.environ.get("FLEETX_SERVING_SPEC_DRAFT", ""),
                self.model, {"params": self.params},
                prefill_bucket=self.prefill_bucket)
            self._proposer.bind(self.slots, self.cache_len)
            # one compile per (k, all_greedy) actually seen: k only drops
            # below spec_k when a lane nears cache capacity
            self._verify_jit = jax.jit(
                self._verify_fn, static_argnums=(6, 7),
                donate_argnums=(1, 2) if donate else ())
            obs_emit("spec_enabled", k=self.spec_k,
                     proposer=self._proposer.name)
        # observability (docs/OBSERVABILITY.md): one env var makes this
        # replica scrapeable, and /healthz turns 503 the instant
        # request_shutdown() flips _shutting_down — the rotate-me-out
        # signal the multi-replica router (ROADMAP item 3) consumes.
        # weakref probe: the health registry must never pin a dead engine.
        obs_http.maybe_start_from_env()
        self._health_name = f"serving_engine_{self.metrics.engine_label}"
        ref = weakref.ref(self)

        def _healthy():
            eng = ref()
            if eng is None:
                return True  # owner gone; finalize unregisters shortly
            # the full healthz body (state/queue_depth/active), not a bare
            # bool: the router and external LBs get a rotate-out REASON
            return eng.health()

        obs_http.register_health(self._health_name, _healthy)
        weakref.finalize(self, obs_http.unregister_health, self._health_name)

    # ------------------------------------------------------------ lifecycle

    def submit(self, prompt, *, max_length: Optional[int] = None,
               min_length: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               decode_strategy: Optional[str] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None, top_p: Optional[float] = None,
               seed: Optional[int] = None, rng_key: Optional[jax.Array] = None,
               on_token=None, queue_ttl_s: Optional[float] = None,
               deadline_s: Optional[float] = None,
               history=None, kv_payloads=None, images=None) -> int:
        """Queue one request; returns its id. Kwargs override the engine's
        ``gen_cfg`` defaults per request; ``seed`` (or a raw ``rng_key``)
        pins this request's private sampling stream, ``on_token`` streams
        ``(request_id, token, finished)`` per decoded token.
        ``queue_ttl_s``/``deadline_s`` override the engine's admission
        limits (0 disables). Raises :class:`QueueFull` when the bounded
        queue is at ``FLEETX_SERVING_MAX_QUEUE`` and :class:`ShuttingDown`
        once :meth:`shutdown`/:meth:`request_shutdown` has been called.

        ``history`` is the ADMIT-WITH-HISTORY seam (the multi-replica
        router's zero-token-loss failover, docs/SERVING.md): tokens this
        request already emitted on another replica before it died. The
        request admits through the replay prefill seam — its
        ``prompt + history[:-1]`` K/V rebuilt in one call, its RNG stream
        advanced to exactly the position ``len(history)`` emitted tokens
        would have consumed (so sampling continues the SAME stream the
        original ``rng_key`` defines — pass the original key) — and
        decoding continues from ``history[-1]``. History tokens count
        against ``max_length`` and ride the final result, but ``on_token``
        fires only for NEWLY decoded tokens (the caller already delivered
        the history). A history that is already terminal (ends in EOS, or
        exhausts ``max_length``) is a caller bug and raises ValueError —
        migrate unfinished requests only.

        ``kv_payloads`` is the DISAGGREGATED-HANDOFF seam (docs/
        SERVING.md "Disaggregated prefill/decode"): the wire-format page
        blobs a PREFILL-role replica's :meth:`export_kv` shipped for
        this prompt, one per page covering the prompt, alongside
        ``history=[t0, ...]`` (the first token that replica emitted).
        The blobs are decoded and validated HERE — a corrupted ship
        raises ValueError at submit, before the request ever queues, so
        the router can fall back to the replay path — and admission
        writes them straight into freshly allocated pages through the
        revive scatter: no prefill forward at all, byte-identical
        decoding to the colocated engine.

        ``images`` (a ``takes_rows`` family: docs/SERVING.md "Rows from a
        tower"): uint8 ``[height, width, 3]`` arrays, one for every image
        whose rows the prompt marks with runs of ``image_token_id`` (``h x
        w`` ids for an image of ``28h x 28w`` pixels at patch 14; a mismatch
        raises here). A request with images cannot be shipped
        (``kv_payloads``, a prefill or decode role: the handoff ships ids)
        nor carry ``history`` (a replay across replicas knows ids alone):
        each raises by name."""
        # the engine's own time between two steps, under its own name
        # (its refusals included: a refused submit has no ``request``)
        with span("serving.submit") as at:
            if self._shutting_down:
                self.metrics.record_drain_reject()
                obs_emit("drain_reject", engine=self.metrics.engine_label)
                raise ShuttingDown(
                    "engine is draining toward shutdown; submit to another "
                    "replica (in-flight requests are finishing under the "
                    "grace window)")
            if self.max_queue and self.scheduler.queue_depth >= self.max_queue:
                # dead entries must not hold live ones out: sweep TTL/deadline
                # expiries before judging the bound (step() normally does this,
                # but a submit burst between ticks sees the stale depth)
                self._expire_queued(self._now())
            if self.max_queue and self.scheduler.queue_depth >= self.max_queue:
                self.metrics.record_reject()
                obs_emit("queue_reject", engine=self.metrics.engine_label,
                         queue_depth=self.scheduler.queue_depth)
                raise QueueFull(
                    f"admission queue is full ({self.scheduler.queue_depth}/"
                    f"{self.max_queue} waiting, "
                    f"{self.cache_manager.active_count}"
                    f"/{self.slots} slots busy); retry later or raise "
                    "FLEETX_SERVING_MAX_QUEUE")
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            at["prompt_len"] = int(prompt.size)
            if prompt.size == 0:
                raise ValueError("empty prompt")
            laid = None
            if images is not None:
                if self._tower is None:
                    raise ValueError(
                        f"model family {self.model_family!r} takes no images "
                        "(capabilities.takes_rows=False)")
                for name, given in (("kv_payloads", kv_payloads is not None),
                                    ("history", history is not None),
                                    (f"role={self.role!r}",
                                     self.role != "both")):
                    if given:
                        raise ValueError(
                            f"a request with images cannot take {name}: the "
                            "handoff between replicas ships token ids, and an "
                            "image's rows have none (docs/SERVING.md \"Rows "
                            "from a tower\")")
            if self._tower is not None:
                # what the ids and the images' SHAPES say, and every refusal (a
                # prompt that marks image rows and brings no image raises): no
                # byte of an image is read on this thread (serving/rows_in.py)
                laid = self._tower.outline(prompt, images)
            g = self.gen_cfg
            strategy = decode_strategy or g.decode_strategy
            if strategy not in ("greedy", "sampling"):
                raise ValueError(
                    f"decode_strategy {strategy!r} not servable by continuous "
                    "batching (beam search needs one-shot generate())")
            limit = min(self.cache_len, self.model.cfg.max_position_embeddings)
            if prompt.size >= limit:
                raise ValueError(
                    f"prompt_len {prompt.size} leaves no decode room "
                    f"(cache/position limit {limit})")
            max_new = int(max_length if max_length is not None
                          else g.max_length)
            if prompt.size + max_new > limit:
                clamped = limit - prompt.size
                logger.warning(
                    "serving: request %d max_length %d clamped to %d "
                    "(prompt %d + limit %d)", self._next_id, max_new, clamped,
                    prompt.size, limit)
                max_new = clamped
            min_new = min(int(min_length if min_length is not None
                              else g.min_length), max_new)
            eos = int(eos_token_id if eos_token_id is not None
                      else (g.eos_token_id if g.eos_token_id is not None
                            else -1))
            vocab = self.model.cfg.vocab_size
            tk = int(top_k if top_k is not None else g.top_k)
            if tk <= 0 or tk >= vocab:
                tk = 0  # no filter (matches _sample's vocab clamp)
            elif tk > self.topk_cap:
                logger.warning(
                    "serving: request %d top_k %d clamped to topk_cap %d "
                    "(FLEETX_SERVING_TOPK_CAP)", self._next_id, tk,
                    self.topk_cap)
                tk = self.topk_cap
            hist = ([] if history is None
                    else [int(t) for t in np.asarray(history,
                                                     np.int64).reshape(-1)])
            if hist:
                if eos >= 0 and hist[-1] == eos:
                    raise ValueError(
                        f"history of {len(hist)} tokens already ends in EOS "
                        f"({eos}) — the request is terminal; do not migrate "
                        "it")
                if max_new <= len(hist):
                    raise ValueError(
                        f"history ({len(hist)} tokens) meets or exceeds the "
                        f"max_length budget ({max_new}) — the request is "
                        "terminal; do not migrate it")
            decoded_pages = None
            if kv_payloads is not None:
                if not hist:
                    raise ValueError(
                        "kv_payloads without history: the prefill replica "
                        "sampled the first token — pass it as history=[t0]")
                need = -(-prompt.size // self.page_size)
                if len(kv_payloads) != need:
                    raise ValueError(
                        f"kv_payloads has {len(kv_payloads)} page blob(s); a "
                        f"{prompt.size}-token prompt at page_size "
                        f"{self.page_size} ships {need}")
                # decode NOW, not at admission: payload_from_bytes verifies
                # the crc32 trailer, so a corrupted ship fails this submit
                # loudly and the request never enters the queue half-armed
                decoded_pages = [
                    HostPageStore.payload_from_bytes(b)
                    if isinstance(b, (bytes, bytearray, memoryview)) else b
                    for b in kv_payloads]
                for leaf in decoded_pages[0]:
                    # a page payload leaf is [..., page_size, lanes]
                    if leaf is not None and leaf.shape[-2] != self.page_size:
                        raise ValueError(
                            f"shipped pages carry {leaf.shape[-2]} rows; this "
                            f"replica's page_size is {self.page_size} — "
                            "disaggregated replicas must agree on page_size")
            rid = at["request"] = self._next_id
            self._next_id += 1
            if rng_key is None:
                rng_key = (jax.random.PRNGKey(int(seed)) if seed is not None
                           else jax.random.fold_in(self._base_key, rid))
            req = Request(
                id=rid, prompt=prompt, max_new_tokens=max(max_new, 1),
                min_new_tokens=min_new, eos_token_id=eos,
                greedy=strategy == "greedy",
                temperature=float(temperature if temperature is not None
                                  else g.temperature),
                top_k=tk,
                top_p=float(top_p if top_p is not None else g.top_p),
                rng_key=rng_key, on_token=on_token,
                submit_time=self._now(),
                queue_ttl_s=float(queue_ttl_s if queue_ttl_s is not None
                                  else self.queue_ttl_s),
                deadline_s=float(deadline_s if deadline_s is not None
                                 else self.deadline_s),
            )
            # admit-with-history: the pre-emitted tokens ARE the request's
            # token list from the start (a queue-expiry or shutdown retirement
            # before admission must still return them — zero token loss), and
            # _admit routes a non-empty list through the replay prefill seam
            req.tokens.extend(hist)
            req.kv_payloads = decoded_pages
            if laid is not None and laid[3]:
                self._tower.lay_out(req, laid)  # keys, patches: on its worker
            self.scheduler.submit(req)
            self.metrics.record_submit()
            return rid

    def step(self) -> Dict:
        """One TRANSACTIONAL scheduler tick: the pure-host bookkeeping
        (scheduler queue, request table, active map, results) is
        snapshotted before any device work; any exception rolls it back to
        the exact pre-tick state and runs the recovery path (module
        docstring), so the caller's ticking loop just keeps ticking.
        Returns a summary dict (``decoded`` counts the lanes whose token
        this step DELIVERED: with a tick in flight those of the tick
        dispatched one step earlier; ``timed_out`` lists this tick's
        deadline victims; ``recovered`` marks a rolled-back-and-recovered
        tick). Raises only :class:`RecoveryExhausted` (the engine is
        dead)."""
        t0 = self._now()
        self._flush_shutdown_event()
        if (self._shutting_down and self._shutdown_deadline is not None
                and t0 >= self._shutdown_deadline
                and (len(self.scheduler) or self._active
                     or self._prefilling or self._prefilled)):
            # grace window over: everything still in flight returns NOW
            # with its partial tokens (the unread tick's among them)
            self._settle("other")
            retired = self._retire_all("shutdown")
            summary = {"admitted": 0, "decoded": 0, "retired": retired,
                       "timed_out": []}
        else:
            # phase-granular transaction: the snapshot re-commits after
            # every successful admission, so a decode fault rolls back ONLY
            # the decode (admitted requests stay admitted — their prefill
            # device work is real and their first token was emitted), and a
            # prefill fault rolls back only the admission in flight. No
            # phase ever commits partially.
            with span("serving.snapshot"):
                snap = self._snapshot()

            def commit():
                with span("serving.snapshot"):
                    fresh = self._snapshot()
                snap.clear()
                snap.update(fresh)

            try:
                with span("serving.tick", tick=self._ticks) as at:
                    summary = self._step_inner(commit)
                    at.update({k: summary[k] for k in _CARRIED})
                if (summary["decoded"] or summary["admitted"]
                        or summary["chunked"]):
                    # a productive device tick proves the engine is healthy
                    # again — re-arm the recovery budget and strike counts
                    self._recoveries_consecutive = 0
                    if summary["decoded"]:
                        self._tick_strikes = 0
            except RecoveryExhausted:
                raise
            except Exception as exc:  # noqa: BLE001 — THE crash-safety seam
                summary = self._handle_tick_fault(snap, exc)
        # the engine's own time behind the tick, under its own name: a
        # device gap there is booked to it, not to the caller (``no span``)
        with span("serving.observe"):
            self._ticks += 1
            self.metrics.observe_tick(self.scheduler.queue_depth,
                                      len(self._active), self._now() - t0)
            self.metrics.observe_pages(self.cache_manager.pages_in_use,
                                       self.cache_manager.usable_pages)
            if self._dram_store is not None:
                self.metrics.observe_host_tier(self._dram_store)
            if self._disk_store is not None:
                self.metrics.observe_disk_tier(self._disk_store)
            self.metrics.observe_queue_tokens(
                self.scheduler.queued_tokens() + sum(
                    r.prompt_len - r.prefill_pos
                    for r in self._prefilling.values()))
            if self.log_every and self._ticks % self.log_every == 0:
                self.metrics.log_snapshot()
            summary.setdefault("recovered", False)
            summary.setdefault("chunked", 0)
            summary["queue_depth"] = self.scheduler.queue_depth
            summary["active_slots"] = len(self._active)
            summary["prefilling"] = len(self._prefilling)
            summary["prefilled"] = len(self._prefilled)
        return summary

    def _step_inner(self, commit=lambda: None) -> Dict:
        """The actual tick body: queued-expiry sweep, prefill work, one
        batched decode step dispatched and the one before it read
        (module docstring "Tick order"), retirements, active-deadline
        sweep. ``commit`` re-bases the transactional snapshot after each
        completed phase (see :meth:`step`): an admission whose first token
        was read, a chunk, a tick's tokens delivered. The prefill work is
        a PASS that takes one chunk-shaped device call: a chunk of the
        prompt mid-prefill, else admissions from the queue's head (with
        chunking, ONE: a long prompt's first chunk or a short prompt's
        one call). With chunking enabled the tick's prefill budget is TWO
        such calls, so the pass runs twice: the prompt mid-prefill is read
        at two chunks a step, the step its last chunk runs in admits the
        next request, and decode never stalls longer than two chunks (the
        ``prefill_stall_ms`` histogram measures it). FIFO holds as it
        did: one prompt mid-prefill, and it is the admission head. A pass
        that admits nobody (nobody queued, the head refused) ends the
        work. Without chunking the one pass admits until a refusal."""
        timed_out = self._expire_queued(self._now())
        admitted = 0
        chunked = 0
        self._refused = None
        self._carried = {"tower": 0, "prefill_rows": 0}
        prefill_t0 = self._now()
        # the step's prefill work, ONE chunk-shaped call a pass: with a
        # chunk size the step's budget is two such calls, so two passes
        for _ in range(2 if self.prefill_chunk else 1):
            if self._prefilling:
                # FIFO holds: the mid-prefill request IS the admission
                # head, so nothing else admits until its chunks finish
                # (or expire)
                self._refused = "slot"
                n, expired = self._chunk_tick()
                chunked += n
                timed_out += expired
                commit()  # chunk progress (prefill_pos) stays committed
                continue
            asked = admitted
            while (len(self.scheduler)
                   and self._can_admit(self.scheduler.peek())):
                req = self.scheduler.pop_next()
                try:
                    self._admit(req)
                except Exception:
                    # a device error of an EARLIER prefill can surface at
                    # this dispatch: it is the failed prefill of the oldest
                    # admission whose token no longer comes back
                    lost = next((first for first in self._first_tokens
                                 if not self._readable(first.tok)), None)
                    if lost is not None:
                        self._fault_ctx = self._fault_of(lost)
                    raise
                admitted += 1
                # the admission before this one is read now, with this
                # one's programs behind it on the device: an admission
                # whose token was read stays admitted
                self._read_first_tokens(commit, keep=1)
                if not (self._first_tokens
                        and self._first_tokens[-1].req is req):
                    # it completed inside _admit (a replay, a shipped one,
                    # a first chunk, a read at once: _sync_cause, probe);
                    # one left unread is to the snapshot still in the queue
                    commit()
                if self.prefill_chunk:
                    self._refused = "slot"
                    break  # one prefill-shaped device call a pass
            if admitted == asked:
                break  # nobody queued, or the head refused: not asked twice
        if self.prefill_chunk and chunked + admitted > 1:
            self.metrics.record_second_chunk()
        if admitted or chunked:
            self.metrics.observe_prefill_stall(self._now() - prefill_t0)
        self._delivered = 0
        retired = []
        if self._proposer is not None:
            if self._active:
                retired = self._tick_decode_spec(commit)
        elif self._active or self._inflight is not None:
            retired = self._tick_decode(commit)
        # no tick was dispatched behind what is still unread (no lane
        # left to decode for): nothing stays unread when step() returns
        self._read_first_tokens(commit, cause="idle")
        # fresh clock: prefill/decode above may have eaten the deadline
        now = self._now()
        if self._inflight is not None and self._overdue(now):
            # a deadline's partial result keeps the token in flight
            retired += self._collect("evict", commit)
        timed_out += self._expire_active(now)
        return {"admitted": admitted, "decoded": self._delivered,
                "chunked": chunked, "retired": retired + timed_out,
                "timed_out": timed_out, **self._carried}

    def cancel(self, request_id: int) -> bool:
        """Cancel a queued or in-flight request: its slot (if any) is freed
        for the next admission THIS instant and its partial output is
        recorded with ``finish_reason="cancelled"``. Returns False when the
        id is unknown or already finished."""
        req = self.scheduler.remove(request_id)
        if req is None:
            if any(r.id == request_id for r in self._active.values()):
                # its token of the tick in flight is part of what it
                # leaves with (and may be its last: then it has finished)
                self._settle("other")
            for r in (list(self._active.values())
                      + list(self._prefilling.values())
                      + list(self._prefilled.values())):
                if r.id == request_id:
                    req = r
                    break
        if req is None:
            return False
        self._evict(req, "cancelled", self._now())
        obs_emit("request_cancelled", request=request_id)
        return True

    def prewarm(self, prompt) -> int:
        """Pull ``prompt``'s prefix pages out of the host/disk tiers into
        the device trie BEFORE this engine takes traffic (the
        autoscaler's scale-up pre-warm, docs/SERVING.md "Per-tenant QoS &
        autoscaling"). A fresh replica sharing a :class:`DiskPageStore`
        with the fleet starts with a cold device trie but a warm store;
        this revives the longest already-persisted prefix through the
        normal alloc path (revived pages carry real K/V) and immediately
        frees the lane, parking the pages zero-ref-warm in the trie — so
        the replica's first real request prefix-hits instead of
        re-prefilling. Returns the number of prefix tokens now warm
        (0: no prefix cache / nothing persisted / pool busy).

        Deliberately NEVER registers fresh pages: only pages revived
        with actual K/V may enter the trie, or later matches would serve
        garbage."""
        if not self.prefix_cache:
            return 0
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0 or prompt.size >= self.cache_len:
            return 0
        pool = self.cache_manager.pool
        chunks = pool._chunks(prompt)
        path = pool._match_path(chunks)
        warm = pool._match_host(chunks, path)
        covered = len(path) + len(warm)
        if covered == 0:
            return 0
        # alloc() shares at most (n-1)//page_size full chunks, so to
        # claim all `covered` warm chunks the probe prompt must span one
        # token PAST them (capped by the real prompt)
        n = min(int(prompt.size), covered * self.page_size + 1)
        if not self.cache_manager.can_admit(prompt[:n]):
            return 0
        got = self.cache_manager.alloc(-1, prompt[:n])
        if got is None:
            return 0
        lane, shared = got
        # free() parks the revived (now zero-ref) pages warm in the trie
        self.cache_manager.free(lane)
        if shared:
            obs_emit("prefix_prewarmed", engine=self.metrics.engine_label,
                     tokens=int(shared))
        return int(shared)

    def _expire_queued(self, now):
        """Retire queued requests whose queue-TTL/deadline passed (they
        never get a slot; ``finish_reason="timeout"``, empty tokens)."""
        out = []
        for req in self.scheduler.pop_expired(now):
            self._finalize(req, "timeout", now)
            obs_emit("request_timeout", request=req.id, where="queue")
            out.append(req.id)
        return out

    def _overdue(self, now) -> list:
        """The in-flight requests past their total deadline."""
        return [req for req in self._active.values()
                if req.deadline_s and now - req.submit_time > req.deadline_s]

    def _expire_active(self, now):
        """Retire in-flight requests past their total deadline, freeing
        their slots; partial tokens are kept in the result."""
        out = []
        for req in self._overdue(now):
            self._evict(req, "timeout", now)
            obs_emit("request_timeout", request=req.id, where="active")
            out.append(req.id)
        return out

    def _evict(self, req: Request, reason: str, now: float) -> None:
        """THE mid-flight retirement path (cancel / deadline / callback
        error): deactivate the request's decode lane on device if it holds
        one, free the slot, record the partial result."""
        if req.slot is not None:
            self._state = self._deactivate_jit(
                self._state, jnp.asarray(req.slot, jnp.int32))
        self._finalize(req, reason, now)

    # ------------------------------------------------------- crash safety

    def _snapshot(self):
        """Capture the pure-host bookkeeping a tick can mutate. Device
        state is deliberately NOT captured: a failed device call may have
        consumed donated buffers, so rollback restores host truth and
        :meth:`recover` rebuilds the device side from it. Metrics stay
        monotonic (a rolled-back tick's gauge samples are not unwound)."""
        # an admission whose first token is unread is NOT yet admitted: a
        # rollback drops its programs with the device state and puts it
        # back at the queue's head, as it was before its admission
        unread = [first.req for first in self._first_tokens]
        active = {slot: r for slot, r in self._active.items()
                  if not any(r is u for u in unread)}
        reqs = (list(self.scheduler.snapshot()) + list(active.values())
                + list(self._prefilling.values())
                + list(self._prefilled.values()))
        return {
            "queue": self.scheduler.snapshot(),
            "unread": unread,
            "active": active,
            "prefilling": dict(self._prefilling),
            "prefilled": dict(self._prefilled),
            "results": dict(self._results),
            # per-request mutable fields the tick touches; tokens rolls
            # back by truncating to its pre-tick length (the list object
            # itself is kept, appends are what a failed tick added).
            # prefill_pos/phase cover chunked-prefill progress, so a
            # mid-chunk fault rolls the request back to its exact
            # pre-tick chunk position (the pages a chunk wrote are device
            # state — NOT captured; recovery requeues mid-prefill requests
            # and restarts them); spec_proposed/accepted cover the
            # speculative draft counters a mid-verify fault would have
            # advanced
            "reqs": [(r, r.slot, r.admit_time, r.first_token_time,
                      len(r.tokens), r.prefill_pos, r.phase,
                      r.spec_proposed, r.spec_accepted) for r in reqs]
            + [(r, None, None, None, 0, 0, "queued", r.spec_proposed,
                r.spec_accepted) for r in unread],
        }

    def _restore(self, snap) -> None:
        self.scheduler.restore(snap["queue"])
        for req in reversed(snap["unread"]):
            self.scheduler.requeue(req)
        self._active = snap["active"]
        self._prefilling = snap["prefilling"]
        self._prefilled = snap["prefilled"]
        self._results = snap["results"]
        for (r, slot, admit_t, first_t, ntok, ppos, phase, sprop,
             sacc) in snap["reqs"]:
            r.slot = slot
            r.admit_time = admit_t
            r.first_token_time = first_t
            r.prefill_pos = ppos
            r.phase = phase
            r.spec_proposed = sprop
            r.spec_accepted = sacc
            del r.tokens[ntok:]

    def _handle_tick_fault(self, snap, exc: Exception) -> Dict:
        """Rollback + recovery + escalation for one failed tick. Token
        streams are untouched (nothing the failed tick produced was
        committed); the queue and every request are exactly pre-tick."""
        ctx, self._fault_ctx = self._fault_ctx, None
        # the unread tick goes with the device state it came from: its
        # tokens never reached host truth, so replay computes them again;
        # so do the unread first tokens, whose requests the snapshot holds
        # as queued
        self._inflight = None
        self._first_tokens.clear()
        with span("serving.rollback", tick=self._ticks):
            self._restore(snap)
        victim = ctx[1] if ctx else None
        obs_emit("tick_fault", tick=self._ticks, error=type(exc).__name__,
                 during_prefill=bool(ctx), request=victim)
        logger.error(
            "serving: tick %d failed (%s: %s)%s; host state rolled back, "
            "running replay recovery", self._ticks, type(exc).__name__, exc,
            f" during prefill of request {victim}" if ctx else "")
        if ctx:
            self._prefill_strikes[victim] = (
                self._prefill_strikes.get(victim, 0) + 1)
        else:
            self._tick_strikes += 1
        retired = list(self.recover())
        if ctx and self._prefill_strikes.get(victim, 0) >= 2:
            # a prefill that failed, survived a recovery, and failed again
            # is a poison prompt — and unlike a decode fault, the culprit
            # is already known: the request being admitted
            req = self.scheduler.remove(victim)
            if req is not None:
                logger.error(
                    "serving: quarantining request %d — its prefill failed "
                    "%d times across a recovery; finish_reason='error'",
                    victim, self._prefill_strikes[victim])
                self._finalize(req, "error", self._now())
                self.metrics.record_poison()
                obs_emit("poison_retired", request=victim, via="prefill")
                retired.append(victim)
            self._prefill_strikes.pop(victim, None)
        elif not ctx and self._tick_strikes >= 2:
            # the decode tick failed again right after a recovery: some
            # active request is poison — bisect to find it
            retired += self._bisect_poison()
            self._tick_strikes = 0
        return {"admitted": 0, "decoded": 0, "retired": retired,
                "timed_out": [], "recovered": True}

    def recover(self):
        """Replay recovery: rebuild the device caches, lane table, and
        page pool from host truth, re-prefilling every active request's
        ``prompt + emitted tokens`` (prefix-trie sharing makes common
        prompts one prefill) and reconstructing its decode-lane scalars —
        including the per-request RNG stream position, so sampling
        requests also resume byte-identically. Public: call it after an
        external device reset too. The DEVICE warm prefix cache (retired
        requests' parked pages) is dropped — a correctness-neutral loss —
        but the host spill tier survives: its entries are keyed by token
        content, so the rebuilt pool revives them on the next match.
        Mid-prefill (chunked) requests requeue at the head and restart.
        Returns the ids of requests retired because their own replay
        failed (their fault followed them into recovery — poison)."""
        if self._inflight is not None:
            # called from outside a failed tick: the unread tick's tokens
            # join host truth if the device still gives them, else replay
            # computes them again (nothing of it was emitted either way)
            try:
                self._collect("other")
            except Exception:  # noqa: BLE001 — the device may be gone
                logger.exception(
                    "serving: the tick in flight could not be read before "
                    "recovery; replay recomputes its tokens")
        self._recoveries_consecutive += 1
        self.metrics.record_recovery()
        if self._recoveries_consecutive > self.max_recoveries:
            # the engine is declaring itself dead — flip /healthz to 503
            # BEFORE raising so the router stops sending traffic to a
            # replica whose every further step will fail
            self._dead = True
            raise RecoveryExhausted(
                f"{self._recoveries_consecutive - 1} consecutive recoveries "
                f"without a productive tick (FLEETX_SERVING_MAX_RECOVERIES="
                f"{self.max_recoveries}); the fault is not request-shaped — "
                "restart the engine/device")
        with span("serving.recover",
                  recovery=self.metrics.engine_recoveries):
            old_active = sorted(self._active.items())
            self._active = {}
            # parked (prefilled, awaiting export) requests replay like
            # active ones — their KV died with the device cache — then
            # re-park with the lane deactivated, still export-ready
            old_parked = sorted(self._prefilled.items())
            self._prefilled = {}
            # mid-prefill (chunked) requests: their partial KV died with
            # the device cache and ZERO tokens were emitted, so they go
            # back to the queue HEAD (they were the head when admitted)
            # and restart chunked prefill — byte-identity is structural,
            # and the host tier below keeps their shared prefix cheap.
            # Arrival order (the ids') holds behind an OLDER admission that
            # a rollback has put back there (its last chunk ran in the same
            # step, ahead of this one's admission, its token was unread)
            back = list(self._prefilling.values())
            newest = max((r.id for r in back), default=-1)
            while len(self.scheduler) and self.scheduler.peek().id < newest:
                back.append(self.scheduler.pop_next())
            for req in sorted(back, key=lambda r: r.id, reverse=True):
                req.slot = None
                req.prefill_pos = 0
                req.phase = "queued"
                self.scheduler.requeue(req)
            self._prefilling = {}
            self._tables_dev = None
            self._tables_version = -1
            self._state = self._replicate(self._init_state())
            if self._tower is not None:
                self._tower.reset()
            # the HOST spill tier survives the rebuild: its entries are
            # keyed by token-chunk path, not trie-node identity, so
            # replayed/requeued prompts revive them from the new pool
            # (only the DEVICE warm cache is a recovery loss)
            self.cache_manager = PagedKVCacheManager(
                self.model, self.slots, self.cache_len, self.num_pages,
                self.page_size, prefix_cache=self.prefix_cache,
                host_store=self._host_store,
                window_span=self.prefill_chunk)
            # the rebuilt device cache re-commits onto the SAME mesh
            # layout — host truth is mesh-agnostic, the layout is not
            self.cache_manager.cache = self._shard_cache(
                self.cache_manager.cache)
            if self._proposer is not None:
                # draft-lane state is device-adjacent: drop it and let
                # the next propose() rebuild lazily from host truth
                # (deterministic, so post-recovery drafts — and the
                # verified streams — stay byte-identical)
                self._proposer.reset()
            retired = []
            for _, req in old_active:
                req.slot = None
                try:
                    self._replay(req)
                except Exception:  # noqa: BLE001 — isolate, don't cascade
                    logger.exception(
                        "serving: request %d failed its own replay during "
                        "recovery; quarantining it (finish_reason='error', "
                        "%d partial tokens kept)", req.id, len(req.tokens))
                    if req.slot is not None:
                        self.cache_manager.free(req.slot)
                        req.slot = None
                    self._finalize(req, "error", self._now())
                    self.metrics.record_poison()
                    obs_emit("poison_retired", request=req.id, via="replay")
                    retired.append(req.id)
                    continue
                self._active[req.slot] = req
            for _, req in old_parked:
                req.slot = None
                try:
                    self._replay(req)
                except Exception:  # noqa: BLE001 — isolate, don't cascade
                    logger.exception(
                        "serving: parked request %d failed its replay "
                        "during recovery; quarantining it "
                        "(finish_reason='error')", req.id)
                    if req.slot is not None:
                        self.cache_manager.free(req.slot)
                        req.slot = None
                    self._finalize(req, "error", self._now())
                    self.metrics.record_poison()
                    obs_emit("poison_retired", request=req.id, via="replay")
                    retired.append(req.id)
                    continue
                # _replay installs an ACTIVE lane; a parked request must
                # stay off the decode tick until export_kv() ships it
                self._state = self._deactivate_jit(
                    self._state, jnp.asarray(req.slot, jnp.int32))
                req.phase = "prefilled"
                self._prefilled[req.slot] = req
        obs_emit("engine_recovery", number=self.metrics.engine_recoveries,
                 replayed=len(self._active), quarantined=len(retired))
        logger.warning(
            "serving: recovery #%d complete — %d request(s) replayed, %d "
            "quarantined", self.metrics.engine_recoveries,
            len(self._active), len(retired))
        return retired

    def _replay(self, req: Request) -> None:
        """Re-admit one in-flight request into the rebuilt engine: prefill
        its full history (all K/V the decode loop had written: prompt plus
        every emitted token except the last, whose K/V write is the next
        tick's job) and reinstall its lane scalars with ``last_tok`` = the
        last emitted token, ready to decode the next one."""
        n = len(req.tokens)
        history = np.concatenate(
            [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
        req.staged = set()
        alloc = self.cache_manager.alloc(req.id,
                                         self._trie_keys(req, history))
        if alloc is None:
            raise RuntimeError(
                f"replay alloc failed for request {req.id} "
                f"({len(history)} history tokens; "
                f"{self.cache_manager.pool.free_pages} pages free)")
        lane, shared = alloc
        req.slot = lane
        # a window class holds the window plus one chunk: its history is
        # written chunk by chunk, as its prefill was
        suffix = history[shared:]
        step = self.prefill_chunk if self.window_pages else len(suffix)
        for at in range(0, max(len(suffix), 1), max(step, 1)):
            self._paged_prefill_call(req, suffix[at:at + step],
                                     shared + at, lane, replay=True)
        self._register_prefix(req)
        # reconstruct the request's RNG stream position: one split at
        # admit, one per decode tick it was active in (greedy requests
        # never consume their stream, so the value is irrelevant there)
        carry = req.rng_key
        if not req.greedy:
            carry = jax.random.split(carry)[1]
            for _ in range(n - 1):
                carry = jax.random.split(carry)[1]
        self._install_lane(
            req, tok=int(req.tokens[-1]), length=len(history), decoded=n,
            active=True, carry_key=carry)

    def _probe_fails(self, slots) -> bool:
        """Run one NON-COMMITTING decode tick over a subset of the active
        lanes (outputs discarded; ``_probe_jit`` never donates, so the
        committed cache/state buffers are untouched). True iff the device
        call — or the poison injector — raised for this subset."""
        reqs = [self._active[s] for s in slots]
        mask = np.zeros(self.slots, bool)
        mask[list(slots)] = True
        st = dict(self._state)
        st["active"] = self._state["active"] & jnp.asarray(mask)
        all_greedy = all(r.greedy for r in reqs)
        ids = [r.id for r in reqs]
        # operands bound on the main thread (same zombie-safety argument as
        # _tick_decode: an abandoned probe must never see post-recovery
        # objects)
        cache_in, tables_in = self.cache_manager.cache, self._device_tables()

        def run():
            faults.on_serving_batch(ids)
            out = self._probe_jit(self.params, cache_in, st, tables_in,
                                  all_greedy)
            return jax.block_until_ready(out)

        try:
            self._run_device(run)
            return False
        except Exception:  # noqa: BLE001 — a probe exists to catch these
            return True

    def _bisect_poison(self):
        """Binary-search the active set for the request whose presence
        kills the decode step; retire it with its partial tokens. Finds
        one poison per escalation — multiple poisons fall out across
        successive escalations. Returns the retired ids ([] when the
        failure does not reproduce under probing, e.g. a transient)."""
        if not self._active:
            return []
        suspects = sorted(self._active)
        if not self._probe_fails(suspects):
            logger.warning(
                "serving: decode failures did not reproduce under probing "
                "(transient device fault?); no quarantine")
            return []
        while len(suspects) > 1:
            half = suspects[:len(suspects) // 2]
            suspects = (half if self._probe_fails(half)
                        else suspects[len(suspects) // 2:])
        slot = suspects[0]
        req = self._active[slot]
        if not self._probe_fails([slot]):
            logger.warning(
                "serving: bisection could not pin the failure to a single "
                "request (fault needs a specific combination?); no "
                "quarantine this round")
            return []
        logger.error(
            "serving: quarantining poison request %d (lane %d) isolated by "
            "bisection; finish_reason='error', %d partial token(s) kept — "
            "neighbors continue untouched", req.id, slot, len(req.tokens))
        self._evict(req, "error", self._now())
        self.metrics.record_poison()
        obs_emit("poison_retired", request=req.id, via="bisection")
        return [req.id]

    def _run_device(self, fn):
        """Run one device call under the hung-tick watchdog. With
        ``FLEETX_SERVING_TICK_TIMEOUT_S`` unset this is a direct call
        (zero overhead); with a timeout the call runs on a persistent
        monitor-thread executor and exceeding the budget raises
        :class:`TickTimeout` into the transactional-tick rollback. The
        abandoned call's thread is orphaned (a truly hung XLA call cannot
        be interrupted from Python) and its buffers are never reused —
        recovery rebuilds fresh ones."""
        if self.mesh is not None:
            # trace-time mesh context (flash dispatch + logical rules);
            # entered INSIDE the callable so the watchdog's worker thread
            # sees it too (contexts do not cross executor threads)
            inner = fn

            def fn():
                with self._mesh_context():
                    return inner()

        if not self.tick_timeout_s or self.tick_timeout_s <= 0:
            return fn()
        import concurrent.futures

        if self._watchdog is None:
            self._watchdog = concurrent.futures.ThreadPoolExecutor(
                1, thread_name_prefix="fleetx-serving-watchdog")
        fut = self._watchdog.submit(fn)
        try:
            return fut.result(timeout=self.tick_timeout_s)
        except concurrent.futures.TimeoutError:
            self._watchdog.shutdown(wait=False)  # abandon the zombie call
            self._watchdog = None
            self.hang_diagnostics = {
                "tick": self._ticks,
                "timeout_s": self.tick_timeout_s,
                "active_requests": sorted(r.id for r in
                                          self._active.values()),
                "queue_depth": self.scheduler.queue_depth,
                "recoveries": self.metrics.engine_recoveries,
            }
            obs_emit("tick_timeout", tick=self._ticks,
                     timeout_s=self.tick_timeout_s)
            logger.error(
                "serving: device tick exceeded FLEETX_SERVING_TICK_TIMEOUT_S"
                "=%.3fs; diagnostics banked in engine.hang_diagnostics, "
                "abandoning the call and recovering", self.tick_timeout_s)
            raise TickTimeout(
                f"device tick exceeded {self.tick_timeout_s}s "
                "(hung device step; see engine.hang_diagnostics)") from None

    # ----------------------------------------------------- graceful drain

    def request_shutdown(self, grace_s: Optional[float] = None) -> None:
        """Flip the engine into draining mode: new submits reject with
        :class:`ShuttingDown`, ticking continues so in-flight and queued
        requests finish, and once ``grace_s`` (default
        ``FLEETX_SERVING_GRACE_S``) elapses the remainder is retired with
        partial tokens. Idempotent and async-signal-safe (flag writes
        only) — exactly what a SIGTERM handler may do."""
        if self._shutting_down:
            return
        self._shutting_down = True
        grace = self.grace_s if grace_s is None else float(grace_s)
        self._shutdown_deadline = self._now() + max(grace, 0.0)
        # the shutdown event is emitted by the next step(), NOT here: this
        # method is async-signal-safe (flag writes only) and the event
        # log/registry take locks a signal context must never acquire
        self._shutdown_event_pending = True
        logger.warning(
            "serving: shutdown requested — admission stopped, draining %d "
            "active + %d queued request(s) under a %.1fs grace window",
            len(self._active), self.scheduler.queue_depth, max(grace, 0.0))

    def shutdown(self, grace_s: Optional[float] = None
                 ) -> Dict[int, ServingResult]:
        """Graceful drain to completion: :meth:`request_shutdown`, tick
        until every request finished or the grace window closed (then
        retire the rest with ``finish_reason="shutdown"`` and partial
        tokens), and return-and-clear ALL results — every request that was
        in flight or queued gets a terminal result. The checkpoint-safe
        shutdown seam the multi-replica router drains replicas through."""
        self.request_shutdown(grace_s)
        # an idle engine drains without a single tick, so flush the
        # deferred shutdown event here too (step() flushes it otherwise)
        self._flush_shutdown_event()
        while (len(self.scheduler) or self._active or self._prefilling
               or self._prefilled or self._inflight is not None):
            self.step()  # the deadline check inside step() retires leftovers
        out, self._results = self._results, {}
        return out

    def _flush_shutdown_event(self) -> None:
        """Emit the shutdown event request_shutdown deferred (it may run
        in a signal context, where the event log's locks are off-limits).
        Called from step() and shutdown() — always outside signals."""
        if self._shutdown_event_pending:
            self._shutdown_event_pending = False
            obs_emit("shutdown", engine=self.metrics.engine_label,
                     active=len(self._active),
                     queued=self.scheduler.queue_depth)

    def _retire_all(self, reason: str):
        """Retire every queued and in-flight request right now (grace
        window closed): queued requests return empty, in-flight ones their
        partial tokens."""
        now = self._now()
        retired = []
        for req in self.scheduler.drain_all():
            self._finalize(req, reason, now)
            retired.append(req.id)
        for req in (list(self._active.values())
                    + list(self._prefilling.values())
                    + list(self._prefilled.values())):
            self._evict(req, reason, now)
            retired.append(req.id)
        return retired

    def install_sigterm_handler(self, grace_s: Optional[float] = None):
        """Register a SIGTERM handler that calls :meth:`request_shutdown`
        (flags only — the drain itself happens in whatever step()/drain()
        loop is already running, never inside the signal context) and then
        chains any previously-installed handler, mirroring the Trainer's
        preemption plumbing (core/engine.py). Main thread only, per the
        ``signal`` module's rules. Returns the previous handler."""
        import signal

        prev = signal.getsignal(signal.SIGTERM)

        def on_sigterm(signum, frame):
            self.request_shutdown(grace_s)
            if callable(prev) and prev not in (signal.SIG_IGN,
                                               signal.SIG_DFL):
                prev(signum, frame)

        self._prev_sigterm = prev
        signal.signal(signal.SIGTERM, on_sigterm)
        return prev

    def uninstall_sigterm_handler(self) -> None:
        """Put back whatever SIGTERM handler install displaced."""
        import signal

        if self._prev_sigterm is not None:
            signal.signal(signal.SIGTERM, self._prev_sigterm)
            self._prev_sigterm = None

    def drain(self, max_ticks: Optional[int] = None) -> Dict[int, ServingResult]:
        """Tick until queue and slots are empty (or ``max_ticks``), then
        return-and-clear every finished result since the last drain."""
        n = 0
        while (len(self.scheduler) or self._active or self._prefilling
               or self._inflight is not None):
            self.step()
            n += 1
            if max_ticks is not None and n >= max_ticks:
                break
        out, self._results = self._results, {}
        return out

    def generate_batch(self, input_ids, gen_cfg: Optional[GenerationConfig]
                       = None, rng: Optional[jax.Array] = None):
        """One-shot convenience with ``generate()``'s contract: every row
        of ``input_ids`` [b, prompt_len] becomes a request, and the result
        is the [b, prompt_len + max_length] token buffer (pad fill after
        EOS). Greedy rows are byte-identical to one-shot ``generate()``;
        sampling rows draw from per-row streams split off ``rng``."""
        g = gen_cfg or self.gen_cfg
        ids = np.asarray(input_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        b, prompt_len = ids.shape
        limit = min(self.cache_len, self.model.cfg.max_position_embeddings)
        if prompt_len + g.max_length > limit:
            # one-shot generate()'s contract: a decode that cannot fit the
            # position table (or this engine's lane capacity) is an error
            # here, not the streaming submit()'s clamp-and-warn
            raise ValueError(
                f"prompt_len({prompt_len}) + max_length({g.max_length}) "
                f"exceeds the engine's decode limit ({limit}: "
                f"min(cache_len, max_position_embeddings))")
        if rng is None:
            rng = self._base_key
        rids = [
            self.submit(
                ids[i], max_length=g.max_length, min_length=g.min_length,
                eos_token_id=g.eos_token_id, decode_strategy=g.decode_strategy,
                temperature=g.temperature, top_k=g.top_k, top_p=g.top_p,
                rng_key=jax.random.fold_in(rng, i),
            )
            for i in range(b)
        ]
        results = self.drain()
        out = np.full((b, prompt_len + g.max_length), g.pad_token_id,
                      np.int32)
        out[:, :prompt_len] = ids
        for i, rid in enumerate(rids):
            res = results.get(rid)
            if res is None:
                # a retired-without-result request (timed out of the queue
                # before this drain, cancelled concurrently, ...) must not
                # crash the whole batch: its row stays pad, loudly
                logger.error(
                    "serving: generate_batch request %d (row %d) produced "
                    "no result; row left as pad", rid, i)
                continue
            if res.finish_reason not in ("eos", "max_length", "cache_full"):
                logger.warning(
                    "serving: generate_batch request %d (row %d) retired "
                    "with finish_reason=%r after %d token(s); rest of row "
                    "is pad", rid, i, res.finish_reason, len(res.tokens))
            toks = res.tokens
            out[i, prompt_len:prompt_len + len(toks)] = toks
        return jnp.asarray(out)

    def result(self, request_id: int) -> Optional[ServingResult]:
        """Finished result for ``request_id`` (None while in flight)."""
        return self._results.get(request_id)

    def take_result(self, request_id: int) -> Optional[ServingResult]:
        """Remove and return one finished result (None while in flight).
        The per-request sibling of :meth:`drain`'s return-and-clear — a
        router collecting results every tick consumes them one at a time
        without resetting the whole table."""
        return self._results.pop(request_id, None)

    def emitted_tokens(self, request_id: int) -> Optional[list]:
        """Host-truth copy of a live request's emitted tokens (None for
        unknown/finished ids). The router's stream-reconciliation seam:
        after a recovered tick it re-bases its durable per-request history
        on the engine's rolled-back-and-replayed token list — the in-
        process analogue of a streaming client re-syncing its offset. Host
        truth is exact here: the tick in flight is read first."""
        self._settle("other")
        for r in (list(self._active.values())
                  + list(self._prefilling.values())
                  + list(self._prefilled.values())
                  + list(self.scheduler.snapshot())):
            if r.id == request_id:
                return list(r.tokens)
        return None

    # ------------------------------------------- disaggregated prefill

    def prefilled_ready(self) -> list:
        """Request ids parked on this PREFILL-role replica with their
        prompt KV fully written, awaiting :meth:`export_kv`
        (docs/SERVING.md "Disaggregated prefill/decode")."""
        return sorted(r.id for r in self._prefilled.values())

    def export_kv(self, request_id: int) -> list:
        """Ship one parked request's prompt KV: walk its block table for
        the ``ceil(prompt_len / page_size)`` pages covering the prompt,
        read them through the same batched per-leaf device gathers the
        host spill tier uses (int8 scale leaves included), and serialize
        each page in the crc32-trailed wire format. On success the
        request finalizes ``finish_reason="prefilled"`` — its lane and
        pages free (the prompt stays warm in THIS replica's prefix trie)
        — and the blobs return in prompt order, ready for
        ``submit(kv_payloads=..., history=[t0])`` on a decode replica.
        Raises KeyError for an id that is not parked; any export fault
        propagates WITHOUT losing the request (it stays parked, its
        emitted first token stays in the router's durable history), so
        the caller falls back to the replay path."""
        req = next((r for r in self._prefilled.values()
                    if r.id == request_id), None)
        if req is None:
            raise KeyError(
                f"request {request_id} is not parked for export "
                f"(parked: {self.prefilled_ready()})")
        self._settle("other")
        attempt = self._fault_ships
        self._fault_ships += 1
        faults.on_kv_ship(attempt, request_id)
        n_pages = -(-req.prompt_len // self.page_size)
        table = self.cache_manager.pool.tables[req.slot]
        pages = [int(table[i]) for i in range(n_pages)]
        with span("serving.export_kv", request=request_id, pages=n_pages):
            payloads = self.cache_manager.read_pages(pages)
        blobs = [HostPageStore.payload_to_bytes(p) for p in payloads]
        if faults.on_kv_ship_corrupt(attempt):
            # chaos seam: flip one byte mid-blob (past the header) — the
            # crc32 trailer must catch it on the decode side's submit
            mid = len(blobs) // 2
            flipped = bytearray(blobs[mid])
            flipped[len(flipped) // 2] ^= 0xFF
            blobs[mid] = bytes(flipped)
        nbytes = sum(len(b) for b in blobs)
        self.metrics.record_kv_shipped(len(blobs), nbytes)
        del self._prefilled[req.slot]
        self._finalize(req, "prefilled", self._now())
        obs_emit("kv_shipped", request=request_id, pages=len(blobs),
                 bytes=nbytes)
        return blobs

    def health(self) -> Dict:
        """The drain-aware health report (the ``/healthz`` JSON body,
        docs/OBSERVABILITY.md): ``state`` is ``"ok"`` while serving,
        ``"draining"`` once :meth:`request_shutdown` flipped admission
        off (rotate out, results still coming), ``"dead"`` after
        :class:`RecoveryExhausted`/:meth:`declare_dead` (rotate out,
        nothing more is coming). ``queue_depth``/``active`` give the
        load-balancing signal next to the rotate-out reason — the
        contract the multi-replica router and any external LB consume."""
        state = ("dead" if self._dead
                 else "draining" if self._shutting_down else "ok")
        out = {"state": state,
               "role": self.role,
               # model-aware routing (docs/SERVING.md "Heterogeneous
               # fleet"): the served family + capability flags ride the
               # same report, so a router groups replicas per model from
               # the scrape it already performs
               "model": self.model_family,
               "capabilities": self.capabilities.as_dict(),
               "queue_depth": self.scheduler.queue_depth,
               # prefill load prices in TOKENS (prefill cost scales with
               # prompt length, not request count): queued prompts plus
               # the unwritten remainder of any in-flight chunked prefill
               "queue_tokens": self.scheduler.queued_tokens() + sum(
                   r.prompt_len - r.prefill_pos
                   for r in self._prefilling.values()),
               "active": (len(self._active) + len(self._prefilling)
                          + len(self._prefilled)),
               "slots": self.slots,
               "pages_in_use": self.cache_manager.pages_in_use,
               "usable_pages": self.cache_manager.usable_pages}
        # a pool of two classes of page reports both ("pages_in_use" and
        # "usable_pages" above stay the full class's)
        classes = self.cache_manager.class_counters()
        if len(self.capabilities.page_classes) > 1:
            out["page_classes"] = {
                kind: {"pages_in_use": classes[f"pages_in_use_{kind}"],
                       "usable_pages": classes[f"usable_pages_{kind}"]}
                for kind in self.capabilities.page_classes}
        # a pool of two kinds of state reports the bytes held of each
        # (``capabilities.state_kinds`` names them)
        if self._state_rows:
            out["state_bytes"] = {
                "kv": classes["kv_page_bytes_in_use"],
                self._lane_state or "conv": (
                    classes["state_bytes_lanes"]
                    + classes.get("state_bytes_snapshots", 0))}
        return out

    def declare_dead(self) -> None:
        """Mark the engine dead (``health()``/``/healthz`` report
        ``"dead"``) without running its shutdown machinery — the seam for
        a supervisor/router that has decided the process or device behind
        this engine is gone (e.g. the replica-kill chaos path). Ticking a
        declared-dead engine is the caller's bug, not prevented here."""
        self._dead = True

    @property
    def submit_limit(self) -> int:
        """The smallest REJECTED per-request prompt size (the engine
        needs at least one token of decode room below it) — the
        per-model admission bound the router validates against at its
        own submit (serving/model_protocol.py ENGINE_SURFACE)."""
        return min(self.cache_len, self.model.cfg.max_position_embeddings)

    # ------------------------------------------------------------- internals

    def _init_state(self):
        s = self.slots
        return {
            "last_tok": jnp.zeros((s,), jnp.int32),
            "lengths": jnp.zeros((s,), jnp.int32),
            "decoded": jnp.zeros((s,), jnp.int32),
            "active": jnp.zeros((s,), bool),
            "eos": jnp.full((s,), -1, jnp.int32),
            "max_new": jnp.ones((s,), jnp.int32),
            "min_new": jnp.zeros((s,), jnp.int32),
            "greedy": jnp.ones((s,), bool),
            "temperature": jnp.ones((s,), jnp.float32),
            "top_k": jnp.zeros((s,), jnp.int32),
            "top_p": jnp.ones((s,), jnp.float32),
            "rng": jnp.zeros((s, 2), jnp.uint32),
            # (what a lane's rotary position stands past its cache row)
            **({"rope_delta": jnp.zeros((s,), jnp.int32)}
               if self._mrope else {}),
        }

    def _eva_pages(self, cfg, num_pages, prefill_bucket) -> int:
        """Pages of EVA's SUMMARY class (what ``num_pages`` counts for the
        family; by default every lane's whole cache in pooled rows), after
        refusing the shapes its programs cannot take, each with its cause:
        a chunk program never straddles a window's boundary and pools whole
        chunks, and a page of the window class is one chunk."""
        chunk, window = cfg.eva_chunk_size, cfg.eva_window_size
        bucket = (prefill_bucket
                  or _env_int("FLEETX_SERVING_PREFILL_BUCKET", 32))
        if (window % self.prefill_chunk or self.prefill_chunk % bucket
                or bucket % chunk or self.page_size != chunk):
            raise ValueError(
                f"model family {self.model_family!r} has EVA attention "
                f"(window {window}, chunk {chunk}): prefill_chunk "
                f"{self.prefill_chunk} must divide the window (a chunk "
                f"program never straddles a boundary), prefill_bucket "
                f"{bucket} the prefill chunk and hold whole chunks, and "
                f"page_size {self.page_size} be the chunk (a page of the "
                "window class is one chunk)")
        rows = self.cache_len // chunk
        return (num_pages or _env_int("FLEETX_SERVING_PAGES", 0)
                or self.slots * -(-rows // self.page_size) + 1)

    def _dequant_params(self, params):
        """Weight-only-int8 dequant seam, called INSIDE every jitted
        prefill/decode body: a no-op at bf16 (the resident tree is read as
        it is); at int8 it re-expands the {"_q8", "_scale"} leaves so XLA
        fuses the scale multiply into each matmul consumer — HBM holds the
        int8 tree, the float view is a fusion-local temporary."""
        if self.weight_dtype != "int8":
            return params
        from fleetx_tpu.ops.quant import dequantize_tree_int8

        return dequantize_tree_int8(params, dtype=jnp.float32)

    # -------------------------------------------------- mesh sharding seams

    def _mesh_context(self):
        """Trace-time context for meshed device calls: the framework mesh
        registry (so the model's flash-decode dispatch sees the ambient
        mesh and shard_maps the kernels) plus the logical-axis rules (so
        activation constraints resolve). A no-op context unmeshed, and
        free after the first trace per call shape — jit caches skip it."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from flax import linen as nn

        from fleetx_tpu.parallel.mesh import use_mesh

        ctx = contextlib.ExitStack()
        ctx.enter_context(use_mesh(self.mesh))
        ctx.enter_context(nn.logical_axis_rules(list(self._rules)))
        return ctx

    def _shard_params(self, params):
        """device_put the servable tree onto its TP(mp)/FSDP layout. The
        model's own ``nn.Partitioned`` metadata (recovered via an
        eval_shape init) names each param's logical axes; quantized
        ``{"_q8", "_scale"}`` leaves inherit their kernel's spec with
        non-dividing dims dropped (parallel/sharding.py). Boxed trees
        are unboxed first — the committed NamedShardings carry the
        layout from here on."""
        from flax import linen as nn

        from fleetx_tpu.parallel.sharding import serving_param_shardings

        params = jax.tree.map(
            lambda x: x.unbox() if isinstance(x, nn.Partitioned) else x,
            params, is_leaf=lambda x: isinstance(x, nn.Partitioned))
        abstract = jax.eval_shape(lambda: self.model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32)))["params"]
        shardings = serving_param_shardings(abstract, params, self.mesh,
                                            self._rules)
        return jax.tree.map(jax.device_put, params, shardings)

    def _cache_shardings(self, cache):
        """Heads-over-mp NamedShardings for a decode cache tree: every
        K/V leaf (slots or pages AND their int8 scale leaves) carries its
        heads along the LAST axis — h*d lanes with head g at
        ``[g*d, (g+1)*d)``, or h scales — so splitting that axis on
        ``mp`` hands each device whole-head groups; scalars replicate.
        Head divisibility was validated at construction."""
        mp = dict(self.mesh.shape).get("mp", 1)

        def one(leaf):
            if getattr(leaf, "ndim", 0) >= KV_LEAF_RANK and mp > 1:
                spec = [None] * (leaf.ndim - 1) + ["mp"]
                return NamedSharding(self.mesh, P(*spec))
            return NamedSharding(self.mesh, P())

        return jax.tree.map(one, cache)

    def _shard_cache(self, cache):
        """Commit a host/eagerly-built cache tree onto the mesh layout
        (construction, recovery, chunk working caches); identity
        unmeshed."""
        if self.mesh is None:
            return cache
        return jax.tree.map(jax.device_put, cache,
                            self._cache_shardings(cache))

    def _pin_cache(self, cache):
        """In-jit sharding constraint pinning a returned cache tree to
        the heads-over-mp layout, so no device call can drift the cache
        into a gathered/replicated layout between ticks (and donation
        keeps matching buffer for buffer); identity unmeshed."""
        if self.mesh is None:
            return cache
        return jax.lax.with_sharding_constraint(
            cache, self._cache_shardings(cache))

    def _replicate(self, tree):
        """Commit small host-built device state (lane scalars, block
        tables) as mesh-replicated; identity unmeshed."""
        if self.mesh is None:
            return tree
        sh = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def _publish_quant_metrics(self) -> None:
        """Push the precision + mesh config and bytes gauges into the
        metrics facade (labels kv_dtype/weight_dtype/mesh;
        docs/OBSERVABILITY.md). All byte gauges are PER DEVICE: under a
        mesh the cache splits its heads over mp and the params split
        TP/FSDP, so what one device holds is the capacity number.
        Re-call after swapping ``engine.metrics`` (the bench does)."""
        from fleetx_tpu.serving.cache_manager import leaf_device_nbytes

        cfg = self.model.cfg
        mp = 1 if self.mesh is None else dict(self.mesh.shape).get("mp", 1)
        kv_item = 1 if self.kv_dtype == "int8" else jnp.dtype(cfg.dtype).itemsize
        # K + V bytes one cached token costs across every layer ON ONE
        # DEVICE, scales included (one fp32 scale per head vector at
        # int8); heads divide over mp under a mesh
        kv_bytes = cfg.num_layers * (cfg.kv_heads // mp) * 2 * (
            cfg.head_dim * kv_item + (4 if self.kv_dtype == "int8" else 0))
        weight_bytes, by_head_bytes = (
            sum(map(leaf_device_nbytes, leaves)) for leaves in (
                jax.tree.leaves(self.params), _by_head_leaves(self.params)))
        if self.mesh is None:
            self.metrics.set_mesh(1, "-")
        else:
            desc = "x".join(f"{k}{v}" for k, v in self.mesh.shape.items()
                            if v > 1) or "1"
            self.metrics.set_mesh(self.mesh.size, desc)
        self.metrics.set_quant_config(
            self.kv_dtype, self.weight_dtype, kv_bytes, weight_bytes,
            self.cache_manager.cache_nbytes(), by_head_bytes)

    def _admit_fn(self, st, ints, tok, floats, key):
        """Jitted: install one request's scalars (``ints`` as
        ``_install_lane`` packed them, ``floats`` temperature and top_p)
        into slot ``slot`` of the device state — ``decoded=1`` for a
        fresh admission (first token just sampled), ``decoded=n`` when
        replay recovery reinstalls a request that already emitted ``n``
        tokens. The lane's token is ``tok``, the scalar a prefill program
        returned, still on the device, where ``ints`` packs none (-1), and
        the lane is live if it is wanted and that token neither is its
        EOS nor uses up its budget: what the host would decide, had it
        read the token first."""
        (slot, packed, length, decoded, wanted, eos, max_new, min_new, greedy,
         top_k) = ints[:10]
        tok = jnp.where(packed < 0, tok, packed)
        active = ((wanted != 0) & ~((eos >= 0) & (tok == eos))
                  & (decoded < max_new))
        lane = {
            "last_tok": tok, "lengths": length, "decoded": decoded,
            "active": active, "eos": eos, "max_new": max_new,
            "min_new": min_new, "greedy": greedy != 0,
            "temperature": floats[0], "top_k": top_k, "top_p": floats[1],
            "rng": key,
        }
        if self._mrope:  # an eleventh int
            lane["rope_delta"] = ints[10]
        return {name: st[name].at[slot].set(value)
                for name, value in lane.items()}

    def _admission_tokens(self, req: Request) -> np.ndarray:
        """The tokens admission must find storage for: the prompt alone
        for a fresh request, ``prompt + history[:-1]`` for an admit-with-
        history request (the last history token's K/V write is the next
        decode tick's job, exactly the replay contract)."""
        if req.tokens:
            return np.concatenate(
                [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
        return req.prompt

    @staticmethod
    def _trie_keys(req: Request, tokens):
        """What the prefix trie (and the tiers behind it) takes for
        ``tokens`` of ``req``: the ids, or for a prompt with images its
        rows' keys (``rows_in.trie_keys``)."""
        if req.keys is None:
            return tokens
        from fleetx_tpu.serving.rows_in import trie_keys

        return trie_keys(req, tokens)

    def _can_admit(self, req: Request) -> bool:
        """FIFO-head admission judgment: a free decode lane and enough
        free pages for the head's prompt plus any migrated history
        (page-granular admission: total live tokens gate entry, not
        worst-case lane capacity). A too-big head BLOCKS, preserving
        arrival order deterministically, and so does one whose images the
        tower's worker still hashes (rows_in.py ``Tower.keyed``). What the
        head is short of (``keys``, ``lane``, ``pages``: said where it is
        decided) goes on the span as ``refused`` and stays ``_refused``
        for the step's account (:meth:`_lanes_at_dispatch`)."""
        # a dry run of the prefix match, once a tick while the head waits
        with span("serving.can_admit", request=req.id) as at:
            if req.keyed is not None and not self._tower.keyed(req):
                refused = "keys"
            else:
                refused = self.cache_manager.refusal(self._trie_keys(
                    req, self._admission_tokens(req)))
            if refused:
                at["refused"] = self._refused = refused
            return refused is None

    def _device_tables(self):
        """Device copy of the block tables, re-uploaded only when the
        manager's version counter moved."""
        version = self.cache_manager.tables_version
        if version != self._tables_version:
            with span("serving.tables"):
                self._tables_dev = self._replicate(
                    jnp.asarray(self.cache_manager.tables))
            self._tables_version = version
        return self._tables_dev

    def compiled_decode(self, all_greedy: bool = True):
        """The AOT ``Compiled`` object of the decode tick at the engine's
        current operand shapes — its ``cost_analysis()`` (the bench's
        bytes-per-token) and ``as_text()`` (which attention path the tick
        really runs: chip_smoke.py asserts the paged kernel's Mosaic
        call). Lowers from the live operands without running or donating
        them, under the same mesh context the tick traces in; a
        persistent-compile-cache hit after the first tick."""
        with self._mesh_context():
            return self._decode_jit.lower(
                self.params, self.cache_manager.cache, self._state,
                self._device_tables(), all_greedy).compile()

    def _row_mask(self, tokens):
        """``tokens`` ``[batch, rows]``, which rows of a cached forward are
        tokens, for a model whose recurrent state must not take a padded
        row or an idle lane in (models/gpt/mixed_stack.py), or whose
        padded rows must close no chunk (EVA attention, models/gpt/eva.py);
        None for a model that keeps keys and values alone, whose writes of
        such rows are never read."""
        return tokens if self._state_rows or self._eva else None

    def _first_token(self, logits, wants, eos, min_new, greedy,
                     temperature, top_k, top_p, key):
        """Traced tail of every prefill body: sample the first token from
        ``logits`` ``[1, 1, vocab]``, those of the last true prompt
        position, the one row the forward was asked for (EOS suppressed
        while ``min_new`` > 0). A call that wants no token (``wants``
        false: an intermediate chunk, a replay) runs no sampler and
        returns token 0, which nobody reads."""
        def sample(last):
            vocab = last.shape[-1]
            last = jnp.where(
                (jnp.arange(vocab)[None, :] == eos) & (min_new > 0),
                _NEG, last)
            return self.executor.sample(
                last, key[None], greedy[None], temperature[None],
                top_k[None], top_p[None], topk_cap=self.topk_cap)[0]

        with jax.named_scope("sampler"):
            return jax.lax.cond(
                wants, sample, lambda last: jnp.zeros((), jnp.int32),
                logits[0].astype(jnp.float32))

    def _make_paged_prefill(self, bucket_len: int):
        """Jitted paged prefill-on-insert for prompt SUFFIXES bucketed to
        ``bucket_len``: the non-shared tail of the prompt runs a batch-1
        cached forward writing K/V straight into the lane's pages (no
        fresh cache, no scatter), attending the trie-shared prefix pages
        already in place, then samples the first token — the prefix-cache
        compute saving is exactly the skipped ``wpos`` leading tokens."""
        max_pos = self.model.cfg.max_position_embeddings
        # one class of page [pages], two classes [2, pages]; lane-resident
        # state [1 + pages] (the lane before its pages)
        table_shape = self.cache_manager.lane_tables(0).shape
        n_table = int(np.prod(table_shape))
        tower = self._tower

        def prefill(params, cache, ints, floats, key, *stage):
            # as _prefill_ints packed them
            true_len, wpos, eos, min_new, greedy, top_k, wants = ints[:7]
            wants = wants != 0
            table = ints[7:7 + n_table].reshape(table_shape)
            suffix = ints[7 + n_table:][:bucket_len]
            # the admission's one split of the request's stream: the
            # sampler's key, and the carry the lane install takes (the
            # bits of the eager split; a replay call drops both)
            step_key, carry_key = jax.random.split(key)
            params = self._dequant_params(params)
            ids = suffix[None, :]
            # absolute positions wpos.. for the suffix; the right-pad
            # bucket tail is causally invisible and its writes land beyond
            # the live window (or on the trash page) — cache_manager.py
            pos = jnp.minimum(wpos + jnp.arange(bucket_len, dtype=jnp.int32),
                              max_pos - 1)[None, :]
            rows_in = {}
            if self._mrope:  # the rows' own positions, three axes each
                pos = jnp.minimum(ints[-3 * bucket_len:].reshape(
                    3, 1, bucket_len), max_pos - 1)
            if tower is not None:  # the tower's rows, where the ids mark them
                rows_in["input_rows"] = (
                    jax.lax.dynamic_slice_in_dim(
                        stage[0], wpos, bucket_len)[None],
                    ((suffix == tower.image_token_id)
                     & (jnp.arange(bucket_len) < true_len))[None])
            logits, cache = self.executor.forward(
                params, cache, ids, pos,
                self._row_mask((jnp.arange(bucket_len) < true_len)[None]),
                cache_positions=wpos[None],
                # [pages] -> [1, pages]; two classes [2, pages] -> [2, 1, .]
                block_tables=jnp.expand_dims(table, -2),
                # the head runs on the one row the sampler reads, and on
                # none in a call that wants no token
                logit_rows=jnp.where(wants, true_len - 1, -1)[None],
                **rows_in)
            cache = self._pin_cache(cache)
            return cache, self._first_token(
                logits, wants, eos, min_new, greedy != 0, floats[0],
                top_k, floats[1], step_key), carry_key

        return jax.jit(
            prefill, donate_argnums=(1,) if self._donate_cache else ())

    @staticmethod
    def _prefill_ints(tokens, bucket: int, wpos: int, table, *,
                      eos: int = -1, min_new: int = 0, greedy: bool = True,
                      top_k: int = 0, wants_token: bool = False,
                      positions=None) -> np.ndarray:
        """The int32 operand of a prefill call, built on the host: seven
        scalars, the lane's table row (of every class), then ``tokens``
        right-padded to ``bucket``. ``wants_token`` says whether the
        caller reads the call's token: the program runs head and sampler
        only then. The defaults are a replay's: no token wanted, and the
        inert sampler (greedy argmax, no filter, nothing suppressed)
        beside it. ``positions`` ``[3, len(tokens)]`` (a model whose
        positions are not its rows): three more rows of ``bucket`` behind
        the tokens."""
        table = np.asarray(table, np.int32).ravel()
        more = 0 if positions is None else 3 * bucket
        ints = np.zeros(7 + table.size + bucket + more, np.int32)
        ints[:7] = (len(tokens), wpos, eos, min_new, greedy, top_k,
                    wants_token)
        ints[7:7 + table.size] = table
        ints[7 + table.size:][:len(tokens)] = tokens
        if more:
            ints[-more:].reshape(3, bucket)[:, :len(tokens)] = positions
        return ints

    def _prefill_args(self, req: Request, tokens, bucket: int, replay: bool,
                      wpos: int, table):
        """Everything a prefill call uploads before it can be dispatched,
        under one ``serving.prefill_args`` span, each a plain copy of a
        host-built array (``transfers`` counts them: the int32 vector
        with the prompt padded to its bucket, and an admission's float32
        pair): no device program runs here. Returns the operands after
        params and cache: ``(ints, floats, key)``. A replay call (K/V
        only, also an intermediate chunk) wants no token, and its float32
        pair is the engine's resident constant."""
        with span("serving.prefill_args", request=req.id, bucket=bucket,
                  transfers=0) as at:
            positions = None
            if self._mrope:
                from fleetx_tpu.serving.rows_in import row_positions

                positions = row_positions(req, wpos, len(tokens))
            if replay:
                ints = self._prefill_ints(tokens, bucket, wpos, table,
                                          positions=positions)
                floats = self._inert_floats
            else:
                ints = self._prefill_ints(
                    tokens, bucket, wpos, table, eos=req.eos_token_id,
                    min_new=req.min_new_tokens, greedy=req.greedy,
                    top_k=req.top_k, wants_token=True, positions=positions)
                floats = _upload(at, _sampler_floats(req))
            return _upload(at, ints), floats, req.rng_key

    def _guarded_prefill(self, req: Request, fn, args, bucket: int,
                         rows: int, first: bool):
        """One prefill device call through the fault-injection hook;
        stores the returned cache in the cache manager and returns the
        first token with the stream's carry key and the call's program
        number (:meth:`_next_program`). The span's ``rows`` are the tokens
        the call holds (its ``bucket`` less the padding), which the step
        counts as ``prefill_rows``; its ``page_writes`` is the
        model's own predicate on the call's shape (``paged_write.
        page_writes``): the pages a pool and layer that the program
        writes a page at a time, 0 where it writes a row at a time; the
        metrics count the calls each way; ``first`` marks the call a
        bucket's program was minted for (the one that traces and compiles
        it or loads it from the cache: the ``jit.*`` spans inside this
        one say which, docs/OBSERVABILITY.md). Deliberately NOT
        under the hung-tick watchdog: prefill calls legitimately include
        fresh-bucket XLA compiles (seconds), and replay recovery
        re-prefills through here — a watchdog here would misread every
        cold compile as a hang and quarantine healthy requests. The
        watchdog budget is calibrated for the steady-state decode tick,
        the loop that actually wedges."""
        attempt = self._fault_prefills
        self._fault_prefills += 1
        program = self._next_program()
        pages = paged_write.page_writes(1, bucket, self.page_size)
        self._carried["prefill_rows"] += rows
        with span("serving.prefill", request=req.id, bucket=bucket,
                  rows=rows, program=program, page_writes=pages,
                  **self._plan) as at:
            if first:
                at["first"] = True
            faults.on_serving_prefill(attempt, req.id)
            with self._mesh_context():
                cache, tok, carry_key = fn(*args)
        self.cache_manager.cache = cache
        self.metrics.record_prefill_write(pages)
        return tok, carry_key, program

    def _bucket_rows(self, n: int, shared: int) -> int:
        """Rows of the prefill program that takes ``n`` tokens behind
        ``shared``: ``n`` rounded up to the bucket, within the cache."""
        bucket = -(-n // self.prefill_bucket) * self.prefill_bucket
        return min(max(bucket, n), self.cache_len - shared)

    def _scan_rows(self, n: int, shared: int) -> dict:
        """Span fields of a prefill call: over lane-resident state the rows
        its scans or delta rules run over, padding included; over layers with a
        kind what the configuration counts of its chunk kernels' work
        (``block_fields.spans``)."""
        cfg, bucket = self.model.cfg, self._bucket_rows(n, shared)
        fields = {"scan_rows": bucket} if self._lane_state else {}
        if getattr(cfg, "layer_kinds", False):
            fields.update(cfg.spans(n, shared, bucket))
        return fields

    def _paged_prefill_call(self, req: Request, suffix, shared, lane,
                            replay: bool = False):
        """Batch-1 prefill of the non-shared ``suffix`` straight into
        ``lane``'s pages at absolute positions ``shared..``. Admission
        returns ``(first_token, carry_key, sampler_floats)``, all on the
        device, and the call's program number; replay returns None.
        Chunked prefill reuses this call verbatim — an intermediate
        chunk is exactly a ``replay`` call (KV writes only: the program
        runs neither head nor sampler, no rng consumed) at its chunk's
        write offset, and the final chunk is exactly an admission call
        whose ``true_len`` lands on the last prompt token. One compiled
        program a bucket serves both: whether a token is wanted is an
        operand (``_prefill_ints``)."""
        bucket = self._bucket_rows(len(suffix), shared)
        fn = self._prefill_jits.get(bucket)
        first = fn is None
        if first:
            fn = self._prefill_jits[bucket] = \
                self._make_paged_prefill(bucket)
        if not self.cache_manager.prepare_span(lane, shared, len(suffix)):
            raise RuntimeError(
                f"window pages ran dry preparing {len(suffix)} tokens at "
                f"{shared} for request {req.id}")
        ints, floats, key = self._prefill_args(
            req, suffix, bucket, replay, shared,
            self.cache_manager.lane_tables(lane))
        args = (self.params, self.cache_manager.cache, ints, floats, key)
        if self._tower is not None:
            # the images among these rows, encoded now: in this step's
            # prefill slot, right before the program that takes their rows
            self._tower.stage_rows(req, shared, len(suffix))
            args += (self._tower.stage,)
        tok, carry_key, program = self._guarded_prefill(
            req, fn, args, bucket=bucket, rows=len(suffix), first=first)
        self.metrics.record_prefill_call(wants_token=not replay)
        return None if replay else (tok, carry_key, floats, program)

    def _claim_storage(self, req: Request) -> int:
        """Claim a decode lane and its page chain for one admission; sets
        ``req.slot`` and returns the shared-prefix token count (trie +
        host-revived)."""
        with span("serving.claim", request=req.id, shared=0) as at:
            alloc = self.cache_manager.alloc(
                req.id, self._trie_keys(req, req.prompt))
            if alloc is None:  # _can_admit() passed, so this is an
                raise RuntimeError(  # invariant breach — fail loudly
                    f"paged alloc failed after admission check for request "
                    f"{req.id} (prompt {req.prompt_len} tokens; "
                    f"{self.cache_manager.pool.free_pages} pages free)")
            lane, shared = alloc
            req.slot = lane
            pool = self.cache_manager.pool
            self.metrics.record_prefix(
                shared, req.prompt_len,
                int(pool.alloc_counts[lane] - pool.shared_counts[lane]))
            at["shared"] = int(shared)
            return shared

    def _install_lane(self, req: Request, *, tok, length: int,
                      decoded: int, active: bool, carry_key,
                      floats=None) -> int:
        """Install one request's decode-lane scalars into the device
        state (shared by fresh admission, replay recovery and a shipped
        admission): ten int32 in one upload; ``floats`` is the float32
        pair the admission's prefill already sent, uploaded here when no
        such call was made. ``tok`` is the lane's last token: an ``int``
        the host knows (packed with the others), or the device scalar a
        prefill program returned, which the host has not read. ``active``
        is whether the lane is WANTED live; the program also looks at the
        token (``_admit_fn``). Returns the install's program number."""
        program = self._next_program()
        with span("serving.install", request=req.id, transfers=0,
                  program=program) as at:
            on_host = isinstance(tok, int)
            ints = np.asarray(
                [req.slot, tok if on_host else -1, length, decoded, active,
                 req.eos_token_id, req.max_new_tokens, req.min_new_tokens,
                 req.greedy, req.top_k]
                + ([req.rope_delta] if self._mrope else []), np.int32)
            if floats is None:
                floats = _upload(at, _sampler_floats(req))
            self._state = self._admit_jit(
                self._state, _upload(at, ints),
                self._no_token if on_host else tok, floats, carry_key)
        return program

    def _register_prefix(self, req: Request) -> None:
        """Enter the request's prompt pages into the prefix trie (host
        work that runs while its prefill is still on the device)."""
        with span("serving.install", request=req.id):
            self.cache_manager.register_prefix(
                req.slot, self._trie_keys(req, req.prompt))

    def _next_program(self) -> int:
        """The number of the program about to be dispatched: one count
        over every prefill call, lane install, decode tick and verify
        call of this engine's life, never reset and never handed out
        twice (not by a rollback, a replay or a recovery). The DISPATCH
        span records it as ``program``; the WAIT span that reads the
        program's result records it as ``reads``. One device runs its
        programs in the order of their dispatch, so the numbers are the
        order of the device's program line (docs/OBSERVABILITY.md)."""
        self._programs += 1
        return self._programs

    def _fetch(self, name: str, *arrays, **attrs):
        """THE blocking device-to-host read: every result the host waits
        for (the tick's tokens and done flags, a prefill's first token)
        comes through here, under a leaf span ``name``, so the wait is
        never booked to the dispatch span before it. Returns one numpy
        array per device array."""
        with span(name, **attrs):
            return [np.asarray(a) for a in arrays]

    def _admit(self, req: Request) -> None:
        """Admit the FIFO head: claim storage, then either the one-call
        whole-suffix prefill (chunking off, or the non-shared suffix fits
        one chunk — today's path, byte-identical) or enter the
        ``prefilling`` state and run the first chunk. A request carrying
        migrated history (``submit(history=...)``) admits through the
        replay seam instead: one whole-history prefill + lane install
        with the RNG position reconstructed, no callbacks re-fired —
        byte-for-byte the recovery replay of PR 8, aimed at a request
        another replica started. A request carrying SHIPPED page
        payloads skips even that prefill: :meth:`_admit_shipped` writes
        them straight into its fresh pages."""
        if req.kv_payloads is not None:
            self._admit_shipped(req)
            return
        if req.tokens:
            self._fault_ctx = ("prefill", req.id)
            with span("serving.admit", request=req.id,
                      prompt_len=req.prompt_len, history=len(req.tokens)):
                self._replay(req)
            self._fault_ctx = None
            self._prefill_strikes.pop(req.id, None)
            now = self._now()
            req.admit_time = now
            self.metrics.record_admit(now - req.submit_time)
            req.phase = "active"
            self._active[req.slot] = req
            return
        self._fault_ctx = ("prefill", req.id)
        with span("serving.admit", request=req.id,
                  prompt_len=req.prompt_len) as at:
            shared = self._claim_storage(req)
            # the tokens the trie matched, and whether the recurrent state
            # was resumed from the matched pages' tails
            at["matched"] = int(shared)
            at["state_resumed"] = bool(shared and self._state_rows)
            if req.images:
                # the trie has been matched BEFORE any tower call: an image
                # wholly inside the match is neither encoded nor prefilled
                req.staged = set()
                rows = int((req.keys < 0).sum())
                spared = self._tower.skipped(req, shared)
                at.update(images=len(req.images), image_rows=rows,
                          images_skipped=spared)
                self.metrics.record_images(rows, spared)
            if (self.prefill_chunk
                    and req.prompt_len - shared > self.prefill_chunk):
                req.prefill_pos = shared
                req.phase = "prefilling"
                self._prefilling[req.slot] = req
                req.admit_time = self._now()
                self.metrics.record_admit(req.admit_time - req.submit_time)
                self._fault_ctx = None
                self._run_chunk(req)  # this tick's one chunk of budget
                return
            at.update(self.metrics.record_selection(
                self._scan_rows(req.prompt_len - shared, shared)))
            first = self._paged_prefill_call(
                req, req.prompt[shared:], shared, req.slot)
            self._register_prefix(req)
            self._fault_ctx = None
            now = self._now()
            req.admit_time = now
            self.metrics.record_admit(now - req.submit_time)
            self._dispatch_first_token(req, *first)
            if (now - self._probed_at >= _PROBE_PERIOD_S
                    and len(self._first_tokens) == 1):
                # the sampled admission that holds its own wait (nothing
                # older is unread, so this is the reading engine's path)
                self._probed_at = now
                self._read_first_tokens(cause="probe")

    def _admit_shipped(self, req: Request) -> None:
        """Admit a request whose prompt KV arrived from a PREFILL-role
        replica (``submit(kv_payloads=...)``): claim a page chain, write
        the shipped pages through the same batched revive scatter the
        host spill tier uses — zero prefill forwards — register the
        prompt in the prefix trie, and install the decode lane resuming
        from ``history[-1]`` with the RNG carry advanced exactly as the
        prefill replica left it. Byte-identical to colocated decoding by
        construction: the pages hold the very K/V bytes that replica's
        prefill wrote. The payloads are consumed UP FRONT, so if this
        admission faults and the transactional tick rolls it back, the
        requeued request re-admits through the replay seam (a re-prefill
        — slower, never wrong)."""
        payloads, req.kv_payloads = req.kv_payloads, None
        self._fault_ctx = ("prefill", req.id)
        with span("serving.admit_shipped", request=req.id,
                  prompt_len=req.prompt_len, pages=len(payloads)):
            alloc = self.cache_manager.alloc(req.id, req.prompt)
            if alloc is None:  # _can_admit() passed, so this is an
                raise RuntimeError(  # invariant breach — fail loudly
                    f"paged alloc failed after admission check for shipped "
                    f"request {req.id} (prompt {req.prompt_len} tokens; "
                    f"{self.cache_manager.pool.free_pages} pages free)")
            lane, shared = alloc
            req.slot = lane
            # trie/host-revived prefix pages are already populated —
            # revive only the shipped pages beyond them
            start = shared // self.page_size
            table = self.cache_manager.pool.tables[lane]
            entries = [(int(table[i]), payloads[i])
                       for i in range(start, len(payloads))]
            if entries:
                self.cache_manager.revive_pages(entries)
            self._register_prefix(req)
        self._fault_ctx = None
        self._prefill_strikes.pop(req.id, None)
        pool = self.cache_manager.pool
        self.metrics.record_prefix(
            shared, req.prompt_len,
            int(pool.alloc_counts[lane] - pool.shared_counts[lane]))
        self.metrics.record_kv_revived_remote(len(entries))
        now = self._now()
        req.admit_time = now
        self.metrics.record_admit(now - req.submit_time)
        # RNG carry: the prefill replica consumed ONE split sampling t0,
        # plus one per later non-greedy history token — identical to the
        # replay reconstruction (greedy lanes never read the stream)
        n = len(req.tokens)
        carry = req.rng_key
        if not req.greedy:
            for _ in range(n):
                carry = jax.random.split(carry)[1]
        self._install_lane(
            req, tok=int(req.tokens[-1]), length=req.prompt_len + n - 1,
            decoded=n, active=True, carry_key=carry)
        req.phase = "active"
        self._active[req.slot] = req
        obs_emit("kv_revived_remote", request=req.id, pages=len(entries),
                 shared=shared)

    def _run_chunk(self, req: Request) -> None:
        """One prefill chunk for a mid-prefill request. Intermediate
        chunks only write KV (inert sampler, rng untouched); the final
        chunk samples the first token exactly like the one-call path and
        promotes the request to the decode set."""
        start = req.prefill_pos
        end = min(start + self.prefill_chunk, req.prompt_len)
        final = end == req.prompt_len
        tokens = req.prompt[start:end]
        self._fault_ctx = ("prefill", req.id)
        with span("serving.prefill_chunk", request=req.id, start=start,
                  rows=end - start, final=final,
                  **self.metrics.record_selection(
                      self._scan_rows(end - start, start))):
            out = self._paged_prefill_call(req, tokens, start, req.slot,
                                           replay=not final)
        self._fault_ctx = None
        req.prefill_pos = end
        self.metrics.record_prefill_chunk(len(tokens))
        if not final:
            return
        self._register_prefix(req)
        del self._prefilling[req.slot]
        self._dispatch_first_token(req, *out)

    def _chunk_tick(self):
        """Advance the mid-prefill request by ONE chunk this tick —
        after checking its deadlines, so an expired request stops
        burning prefill compute (retired ``finish_reason="timeout"``
        with lane + pages freed; prefix registration only happens at
        completion, so nothing leaks). A request that has not produced
        its first token is still "waiting" in the queue-TTL sense, so
        BOTH limits apply between chunks. Returns ``(chunks_executed,
        timed_out_ids)``."""
        slot = min(self._prefilling)
        req = self._prefilling[slot]
        now = self._now()
        waited = now - req.submit_time
        if ((req.queue_ttl_s and waited > req.queue_ttl_s)
                or (req.deadline_s and waited > req.deadline_s)):
            self._evict(req, "timeout", now)
            obs_emit("request_timeout", request=req.id, where="prefilling")
            return 0, [req.id]
        self._run_chunk(req)
        return 1, []

    def _dispatch_first_token(self, req: Request, tok, carry_key, floats,
                              program: int) -> None:
        """Shared admission tail, the DISPATCH half (module docstring,
        "Tick order"): install the decode lane with the prefill's token
        still on the device, right behind the prefill ``program``, and
        keep the admission in ``_first_tokens`` until
        :meth:`_read_first_tokens` reads it, with a later program behind
        it. Meanwhile the request is where the tick's dispatch sees it as
        live, unless this token is its only one. A PREFILL-role replica
        never decodes: its lane is installed INERT (any stray decode tick
        stays off its pages) and the read parks it. An engine that reads
        every tick at once reads this token at once too."""
        parked = self.role == "prefill"
        installed = self._install_lane(
            req, tok=tok, length=req.prompt_len, decoded=1,
            active=not parked, carry_key=carry_key, floats=floats)
        self._first_tokens.append(
            InflightFirstToken(tok, req, program, installed))
        if req.max_new_tokens > 1 and not parked:
            req.phase = "active"
            self._active[req.slot] = req
        cause = self._sync_cause()
        if cause:
            self._read_first_tokens(cause=cause)

    def _read_first_tokens(self, commit=lambda: None, cause: str = "idle",
                           keep: int = 0) -> int:
        """The READ half: the host sync of every unread first token but
        the newest ``keep``, oldest first, each followed by ``commit`` (an
        admission whose token was read stays admitted, whatever fails
        later in this step). Returns how many were read. One with a
        program dispatched behind its install counts as overlapped; else
        ``cause`` (one of ``inflight.FLUSH_CAUSES``) says why it was read
        with nothing behind it."""
        read = 0
        while len(self._first_tokens) > keep:
            self._read_first_token(self._first_tokens.popleft(), cause)
            commit()
            read += 1
        return read

    def _read_first_token(self, first: InflightFirstToken,
                          cause: str) -> None:
        """Wait for one prefill's first token (``serving.first_token``:
        the host-visible prefill wait, which ``reads`` the request's last
        prefill ``program``), record TTFT, fire the callback, and leave the
        request in the active set, park it, or retire it: the device lane
        is already what the token decided (``_admit_fn``)."""
        req = first.req
        overlapped = self._programs > first.installed
        try:
            tok = int(self._fetch(
                "serving.first_token", first.tok, request=req.id,
                reads=first.program, overlapped=int(overlapped))[0])
        except Exception:
            # the device error of a prefill surfaces here
            self._fault_ctx = self._fault_of(first)
            raise
        if overlapped:
            self.metrics.record_first_token_overlapped()
        else:
            self.metrics.record_first_token_flushed(cause)
        self._prefill_strikes.pop(req.id, None)  # survived its prefill
        now = self._now()
        req.first_token_time = now
        req.tokens.append(tok)
        self.metrics.record_first_token(now - req.submit_time)
        self.metrics.record_tokens(1)
        done_eos = req.eos_token_id >= 0 and tok == req.eos_token_id
        done = done_eos or req.max_new_tokens <= 1
        # callback AFTER the device state is consistent: a raising callback
        # then retires exactly this request and can't leave the slot half-
        # installed
        if not self._emit_token(req, tok, done):
            self._retire_error(req, now)
        elif done:
            self._finalize(req, "eos" if done_eos else "max_length", now)
        elif self.role == "prefill":
            req.phase = "prefilled"
            self._prefilled[req.slot] = req
            obs_emit("prefill_parked", request=req.id,
                     prompt_len=req.prompt_len)

    def _fault_of(self, first: InflightFirstToken):
        """The ``_fault_ctx`` of a first token that does not come back: its
        request's failed prefill, unless the tick dispatched before it is
        unreadable too (then that tick failed first: a failed tick)."""
        tick = self._inflight
        if (tick is None or tick.program > first.program
                or self._readable(tick.tok)):
            return ("prefill", first.req.id)
        return None

    @staticmethod
    def _readable(array) -> bool:
        """Whether a device result still comes back to the host."""
        try:
            np.asarray(array)
            return True
        except Exception:  # noqa: BLE001 — the question this answers
            return False

    def _decode_fn(self, params, cache, st, tables, all_greedy: bool):
        """Jitted: ONE decode token for every slot. An inactive slot
        rides along with its write pinned to the last cache row, which a
        freed lane's zeroed block table re-routes to the trash page: the
        model reads that off the table and hands the decode kernel an
        EMPTY window for the lane (``paged_write.decode_end``), so it
        costs the kernel no step and no copy; a lane that is inactive in
        this tick but owns a real page at the last row (parked, or
        mid-prefill with a request that fills the row) attends as a live
        one. Either way its outputs are ignored. ``tables`` is the device
        block tables. ``all_greedy`` is static — greedy-only ticks take a
        bare argmax and skip the sampler's top-k sort / top-p bisection /
        rng split."""
        params = self._dequant_params(params)
        active = st["active"]
        lengths = st["lengths"]
        max_pos = self.model.cfg.max_position_embeddings
        with jax.named_scope("lanes"):
            wpos = jnp.where(active, lengths, self.cache_len - 1)
            posid = jnp.where(active, jnp.minimum(lengths, max_pos - 1), 0)
            posid = posid[:, None]
            if self._mrope:  # a decoded row: the three axes alike
                posid = jnp.broadcast_to(jnp.where(
                    active, jnp.minimum(lengths + st["rope_delta"],
                                        max_pos - 1), 0)[None, :, None],
                    (3,) + posid.shape)
        logits, cache = self.executor.forward(
            params, cache, st["last_tok"][:, None],
            posid, self._row_mask(active[:, None]),
            cache_positions=wpos,
            block_tables=tables)
        with jax.named_scope("sampler"):
            step = logits[:, -1, :].astype(jnp.float32)
            vocab = step.shape[-1]
            suppress = ((st["decoded"] < st["min_new"])[:, None]
                        & (jnp.arange(vocab)[None, :] == st["eos"][:, None]))
            step = jnp.where(suppress, _NEG, step)
            if all_greedy:
                tok = jnp.argmax(step, axis=-1).astype(jnp.int32)
                new_rng = st["rng"]  # greedy consumes no randomness
            else:
                keys = jax.vmap(functools.partial(jax.random.split, num=2))(
                    st["rng"])
                tok = self.executor.sample(step, keys[:, 0], st["greedy"],
                                           st["temperature"], st["top_k"],
                                           st["top_p"], topk_cap=self.topk_cap)
                new_rng = jnp.where(active[:, None], keys[:, 1], st["rng"])
            new_len = lengths + 1
            decoded = st["decoded"] + 1
            done = active & (
                (tok == st["eos"])
                | (decoded >= st["max_new"])
                | (new_len >= self.cache_len)
            )
            new_st = dict(st)
            new_st["last_tok"] = jnp.where(active, tok, st["last_tok"])
            new_st["lengths"] = jnp.where(active, new_len, lengths)
            new_st["decoded"] = jnp.where(active, decoded, st["decoded"])
            new_st["active"] = active & ~done
            new_st["rng"] = new_rng
        return self._pin_cache(cache), new_st, tok, done

    def _live_lanes(self) -> Dict[int, Request]:
        """The lanes the next tick decodes for, by the host's own count:
        every active lane but those whose LAST token is already in flight
        (the unread tick takes them to ``max_new_tokens`` or to the end of
        the cache, and the program cleared their ``active`` bit itself).
        A lane that sampled EOS in the unread tick is still among them:
        only the token says so, and the device carries it inactive."""
        tick, lengths = self._inflight, self.cache_manager.lengths
        return {slot: req for slot, req in self._active.items()
                if (len(req.tokens)
                    + pending_of(tick, self._first_tokens, slot, req)
                    < req.max_new_tokens)
                and lengths[slot] < self.cache_len}

    def _lanes_at_dispatch(self, batch: int) -> dict:
        """Span fields of a tick: where every lane that is not among the
        ``batch`` it decodes for stands at its dispatch, after the step's
        admission phase. ``lanes_finishing``: the request's LAST token is
        in flight (active and not live, :meth:`_live_lanes`; and one
        admitted for a single token, which is unread); ``lanes_prefilling``:
        held by a prompt mid-prefill, or parked; ``lanes_waiting``: free,
        with a request queued for it, and ``waiting_on`` says what the head
        of the queue was refused for in this step (``_refused``: ``slot``,
        ``pages``, ``keys``; a lane that came free only after the
        admission phase, by a dry pool's read, waits for the next step's
        ``slot``); ``lanes_unasked``: free beyond the queue. With ``batch``
        they add up to the slots."""
        single = sum(1 for first in self._first_tokens
                     if self._active.get(first.req.slot) is not first.req)
        free = self.cache_manager.free_count
        waiting = min(free, len(self.scheduler))
        fields = {"lanes_finishing": len(self._active) - batch + single,
                  "lanes_prefilling": (len(self._prefilling)
                                       + len(self._prefilled)),
                  "lanes_waiting": waiting, "lanes_unasked": free - waiting}
        if waiting:
            refused = self._refused
            fields["waiting_on"] = refused if refused in WAITS else "slot"
        return fields

    def _grow_pages(self, commit=lambda: None) -> list:
        """Grow-on-demand BEFORE the write: any live lane whose next
        position crosses into an unallocated page claims one now; a dry
        pool first reads the tick in flight (a lane it finished gives its
        pages back) and only then retires the request with its partial
        tokens ("cache_full") — deterministic lowest-lane-first order.
        Returns the retired ids."""
        retired = []
        with span("serving.grow"):
            for slot, req in sorted(self._live_lanes().items()):
                if self._active.get(slot) is not req:
                    continue  # the read a dry pool forced (below) finished it
                if self.cache_manager.ensure_page(slot):
                    continue
                if self._inflight is not None or self._first_tokens:
                    retired += self._collect("pool_dry", commit)
                    if (self._active.get(slot) is not req
                            or self.cache_manager.ensure_page(slot)):
                        continue
                self._evict(req, "cache_full", self._now())
                obs_emit("cache_full", request=req.id,
                         tokens=len(req.tokens))
                retired.append(req.id)
        return retired

    def _decode_rows(self, lanes) -> dict:
        """Span fields of a tick over two classes of page: the live cache
        rows its kernel calls read for the lanes it is dispatched for, in
        ONE full layer (every row up to the token being written) and in
        ONE window layer (the window's rows of those); over two kinds of
        state, the rows of one attention layer; over an expert layer that
        holds a share, the pairs its routers choose. Empty with one class
        and one kind."""
        rows = self.cache_manager.lengths[list(lanes)] + 1
        cfg = self.model.cfg
        if self._state_rows or getattr(cfg, "indexed", False):
            # what ONE of its attention layers reads, and
            # the lanes whose lane-resident state the tick advances
            fields = {cfg.rows_span_field: int(rows.sum()),
                      **({"state_lanes": len(lanes)} if self._lane_state
                         else {}), **cfg.span_pairs(self.slots)}
            if cfg.indexed:
                # a lane scores every index key behind its token and
                # attends over the rows the indexer keeps of them
                fields.update(index_rows=int(rows.sum()),
                              selected_rows=int(cfg.selected(rows).sum()))
            return fields
        if not self.window_pages:
            return {}
        cfg = self.model.cfg
        if self._eva:  # what ONE layer attends over, and the chunks closed
            return cfg.eva_spans(rows - 1)
        return {"full_rows": int(rows.sum()), "window_rows": int(
            np.minimum(rows, cfg.sliding_window).sum()),
            **cfg.span_pairs(self.slots)}

    def _decode_kernel_steps(self) -> dict:
        """Span field of every ``serving.decode``: the grid steps of the
        paged decode kernel over all the layers' calls of a tick, a constant
        of the engine's shapes (``cfg.decode_kernel_steps``); none where a
        tick does not run that kernel (off the chip unless forced, latent
        attention's own kernel, heads a mesh does not divide)."""
        from fleetx_tpu.ops.pallas.decode_attention import (
            decode_flash_supported,
            decode_mesh_shardable,
        )

        cfg = self.model.cfg
        if not (cfg.use_flash_attention
                and decode_flash_supported(self.page_size)):
            return {}
        shards = 1
        if self.mesh is not None and self.mesh.size > 1:
            if not decode_mesh_shardable(self.mesh, cfg.num_attention_heads):
                return {}
            shards = dict(self.mesh.shape).get("mp", 1)
        steps = cfg.decode_kernel_steps(self.slots, shards)
        return {"kernel_steps": steps} if steps else {}

    def _sync_cause(self) -> Optional[str]:
        """Why this engine reads every tick before it dispatches the next,
        by what it already is (None: it keeps one in flight): a
        speculative proposer reads the tokens on the host; an armed
        watchdog blocks on the program by design."""
        if self._proposer is not None:
            return "spec"
        return "watchdog" if self.tick_timeout_s > 0 else None

    def _tick_decode(self, commit=lambda: None):
        """Dispatch tick n, THEN read tick n-1 and the first tokens of
        this step's last admissions (module docstring "Tick order").
        Returns the ids retired by the tick that was read."""
        retired = self._grow_pages(commit)
        lanes = self._live_lanes()
        if not lanes:
            # nothing to dispatch for: the lanes left have their last
            # token in flight (drain()'s last tick), or all have gone
            return retired + self._collect("idle", commit)
        all_greedy = all(r.greedy for r in lanes.values())
        active_ids = [r.id for r in lanes.values()]
        attempt = self._fault_ticks
        self._fault_ticks += 1
        # bind the device operands NOW, on the main thread: if the watchdog
        # abandons this call mid-hang and recovery swaps self.cache_manager/
        # self._state, the zombie thread must wake holding the OLD buffers
        # (safe to donate — they are dead) and never touch the recovered
        # ones; _device_tables() also mutates engine state, so it cannot run
        # on the worker thread
        cache_in, state_in = self.cache_manager.cache, self._state
        tables_in = self._device_tables()

        def run():
            # fault hooks INSIDE the guarded call: an injected hang is what
            # the watchdog times, an injected raise unwinds like a real
            # device error (both inert one-flag checks in production)
            faults.on_serving_tick(attempt)
            faults.on_serving_batch(active_ids)
            out = self._decode_jit(self.params, cache_in, state_in,
                                   tables_in, all_greedy)
            if self.tick_timeout_s > 0:
                # surface async device errors inside the watchdog window
                jax.block_until_ready(out)
            return out

        before = self._inflight
        program = self._next_program()
        with span("serving.decode", batch=len(active_ids),
                  empty_lanes=self.slots - len(lanes),
                  **self.metrics.record_lane_steps(
                      len(lanes), self._lanes_at_dispatch(len(lanes))),
                  inflight=int(before is not None), program=program,
                  **self._kernel_steps, **self._plan,
                  **self.metrics.record_selection(self._decode_rows(lanes))):
            cache, st, tok, done = self._run_device(run)
        self.cache_manager.cache = cache
        self._state = st
        # the host's position is the position AS DISPATCHED: the next
        # tick's page growth, window recycling, row counts and tables are
        # reckoned from it while this tick's tokens are still on their way
        self.cache_manager.lengths[list(lanes)] += 1
        self._inflight = InflightTick(tok, done, lanes, program)
        if before is not None:
            # the wait for tick n-1 is HERE, with tick n on the device
            self.metrics.record_tick_overlapped()
            retired += self._deliver(before, commit)
        # and the wait for this step's last admissions, in the order the
        # device runs them: tick n-1, their prefills, tick n
        self._read_first_tokens(commit)
        cause = self._sync_cause()
        if cause:
            retired += self._collect(cause, commit)
        return retired

    def _collect(self, cause: str, commit=lambda: None) -> list:
        """Read the tick in flight NOW, with no tick behind it on the
        device (``cause``: one of ``inflight.FLUSH_CAUSES``), and after it
        the first tokens a step has left unread; nothing to do without
        either. Returns the ids the tick's tokens retired."""
        tick, self._inflight = self._inflight, None
        retired = []
        if tick is not None:
            retired = self._deliver(tick, commit, flushed=cause)
            self.metrics.record_tick_flushed(cause)
        # inside a step, what was admitted behind that tick (a dry pool)
        self._read_first_tokens(commit, cause=cause)
        return retired

    def _settle(self, cause: str) -> None:
        """:meth:`_collect` from OUTSIDE a step() (cancel, export_kv, the
        end of the grace window): no transaction is open, so a read that
        fails is handled as the failed tick it is: nothing of it was
        emitted, the device is rebuilt from host truth."""
        try:
            self._collect(cause)
        except RecoveryExhausted:
            raise
        except Exception as exc:  # noqa: BLE001 — the crash-safety seam
            self._handle_tick_fault(self._snapshot(), exc)

    def _deliver(self, tick: InflightTick, commit,
                 flushed: Optional[str] = None) -> list:
        """The host sync of a tick: wait for its tokens (``serving.fetch``
        ``reads`` the tick's ``program``, and names as ``flushed`` the
        cause of a read with no tick behind it), hand each to the
        request that STILL holds the lane it was dispatched for, retire
        what finished, then ``commit`` (what was emitted stays emitted,
        whatever fails later in this step)."""
        batch = len(tick.lanes)
        why = {"flushed": flushed} if flushed else {}
        tok_np, done_np = self._fetch("serving.fetch", tick.tok, tick.done,
                                      batch=batch, reads=tick.program, **why)
        now = self._now()
        retired = []
        with span("serving.emit", batch=batch):
            for slot, req in tick.lanes.items():
                if self._active.get(slot) is not req:
                    # it left after the dispatch (cancelled, expired,
                    # retired by its callback, EOS one tick earlier): the
                    # token is nobody's, least of all the lane's next
                    # tenant's
                    continue
                t = int(tok_np[slot])
                req.tokens.append(t)
                self._delivered += 1
                self.metrics.record_tokens(1)
                finished = bool(done_np[slot])
                # firewalled callback: a raising on_token retires THIS
                # request only — every neighbor's host token list was
                # already appended this tick and keeps decoding undisturbed
                if not self._emit_token(req, t, finished):
                    self._retire_error(req, now)
                    retired.append(req.id)
                    continue
                if finished:
                    if req.eos_token_id >= 0 and t == req.eos_token_id:
                        reason = "eos"
                    elif len(req.tokens) >= req.max_new_tokens:
                        reason = "max_length"
                    else:
                        reason = "cache_full"
                    self._finalize(req, reason, now)
                    retired.append(req.id)
        commit()
        return retired

    # ------------------------------------------------ speculative decoding

    def _verify_fn(self, params, cache, st, tables, draft, draft_len,
                   k: int, all_greedy: bool):
        """Jitted draft-k-verify-once step (module docstring): ONE
        prefill-shaped forward scores all ``k+1`` positions of every
        lane — ``[last_tok, d1..dk]`` written at the lane's own
        ``cache_positions`` offsets, exactly the multi-token seam
        chunked prefill/replay use — then acceptance runs ON DEVICE so
        the host round-trip stays O(slots·k), not O(vocab).

        Greedy rows keep the longest draft prefix matching the per-
        position argmax (with the per-position ``min_new`` EOS
        suppression the sequential loop would have applied) plus the
        correction/bonus token — byte-identical to k+1 plain ticks by
        construction. Sampling rows run speculative rejection per
        position (accept ``d`` with prob ``p(d)`` — the proposers are
        deterministic, q = 1 — else sample the residual ``(p - q)+``),
        consuming exactly one rng split per EMITTED token so replay's
        stream reconstruction is unchanged. Inactive lanes ride along
        with writes pinned beyond every live window (position clamps
        re-route through zeroed tables to the trash page). Returns
        ``(cache, new_state, out_tokens [b,k+1], n_emit [b],
        n_accepted [b], done [b])``."""
        params = self._dequant_params(params)
        s = k + 1
        active = st["active"]
        lengths = st["lengths"]
        max_pos = self.model.cfg.max_position_embeddings
        # pinned write base for inactive rows: all s positions clamp onto
        # the last logical slot (trash-routed when unallocated)
        with jax.named_scope("lanes"):
            wpos = jnp.where(active, lengths, self.cache_len - 1)
            ids = jnp.concatenate([st["last_tok"][:, None], draft], axis=1)
            posid = jnp.minimum(
                wpos[:, None] + jnp.arange(s, dtype=jnp.int32), max_pos - 1)
            posid = jnp.where(active[:, None], posid, 0)
        logits, cache = self.executor.forward(
            params, cache, ids, posid, None,
            cache_positions=wpos, block_tables=tables)
        with jax.named_scope("sampler"):
            logits = logits.astype(jnp.float32)
            vocab = logits.shape[-1]
            # per-position min_new suppression: position j samples generated
            # token number decoded + j + 1, so EOS is banned while
            # decoded + j < min_new — the condition each sequential tick
            # would have applied
            decoded_at = st["decoded"][:, None] + jnp.arange(s)[None, :]
            suppress = ((decoded_at < st["min_new"][:, None])[:, :, None]
                        & (jnp.arange(vocab)[None, None, :]
                           == st["eos"][:, None, None]))
            logits = jnp.where(suppress, _NEG, logits)
            greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [b, s]
            idx = jnp.arange(s, dtype=jnp.int32)[None, :]
            if all_greedy:
                # vectorized acceptance: position j's target IS what tick j
                # would have emitted, so the emitted run is target[:acc+1]
                # cut at the first EOS inside it; no rng is consumed
                match = ((draft == greedy_tok[:, :k])
                         & (jnp.arange(k)[None, :] < draft_len[:, None]))
                acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
                m0 = acc + 1
                is_eos = greedy_tok == st["eos"][:, None]
                eos_pos = jnp.min(
                    jnp.where(is_eos & (idx < m0[:, None]), idx, s), axis=1)
                m = jnp.minimum(m0, eos_pos + 1)
                acc = jnp.minimum(acc, m)
                out = greedy_tok
                new_rng = st["rng"]  # greedy consumes no randomness
            else:
                # per-position target distributions through THE shared
                # per-row sampler filter pipeline (rows repeated per
                # position: row b*s + j filters position j of lane b)
                b = logits.shape[0]
                filt = self.executor.filter(
                    logits.reshape(b * s, vocab),
                    jnp.repeat(st["temperature"], s),
                    jnp.repeat(st["top_k"], s),
                    jnp.repeat(st["top_p"], s),
                    topk_cap=self.topk_cap).reshape(b, s, vocab)
                p = jax.nn.softmax(filt, axis=-1)
                split2 = jax.vmap(functools.partial(jax.random.split, num=2))
                alive = active
                carry = st["rng"]
                m = jnp.zeros_like(lengths)
                acc = jnp.zeros_like(lengths)
                cols = []
                for j in range(s):
                    pair = split2(carry)
                    step_key, next_carry = pair[:, 0], pair[:, 1]
                    sub = split2(step_key)
                    d = (draft[:, j] if j < k
                         else jnp.zeros_like(st["last_tok"]))
                    has_draft = j < draft_len
                    pj = p[:, j, :]
                    p_d = jnp.take_along_axis(pj, d[:, None], axis=1)[:, 0]
                    u = jax.vmap(jax.random.uniform)(sub[:, 0])
                    # residual (p - q)+ of a deterministic (one-hot) draft:
                    # p with the draft token zeroed; log turns zeros to -inf
                    resid = jnp.where(jnp.arange(vocab)[None, :] == d[:, None],
                                      0.0, pj)
                    samp_rej = jax.vmap(jax.random.categorical)(
                        sub[:, 1], jnp.log(resid))
                    samp_direct = jax.vmap(jax.random.categorical)(
                        sub[:, 1], filt[:, j, :])
                    accept_s = has_draft & (u < p_d)
                    tok_s = jnp.where(accept_s, d,
                                      jnp.where(has_draft, samp_rej,
                                                samp_direct))
                    accept_g = has_draft & (d == greedy_tok[:, j])
                    accept_j = jnp.where(st["greedy"], accept_g, accept_s)
                    tok_j = jnp.where(st["greedy"], greedy_tok[:, j],
                                      tok_s).astype(jnp.int32)
                    cols.append(jnp.where(alive, tok_j, 0))
                    m = m + alive
                    acc = acc + (alive & accept_j)
                    # one split per emitted token, every active row (the
                    # mixed-tick baseline advances greedy rows' streams too)
                    carry = jnp.where(alive[:, None], next_carry, carry)
                    alive = alive & accept_j & (tok_j != st["eos"])
                out = jnp.stack(cols, axis=1)
                new_rng = carry
            m = jnp.where(active, m, 0)
            new_len = lengths + m
            decoded = st["decoded"] + m
            last = jnp.take_along_axis(
                out, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
            last = jnp.where(active & (m > 0), last, st["last_tok"])
            done = active & (
                (last == st["eos"])
                | (decoded >= st["max_new"])
                | (new_len >= self.cache_len)
            )
            new_st = dict(st)
            new_st["last_tok"] = last
            new_st["lengths"] = jnp.where(active, new_len, lengths)
            new_st["decoded"] = jnp.where(active, decoded, st["decoded"])
            new_st["active"] = active & ~done
            new_st["rng"] = new_rng
        return self._pin_cache(cache), new_st, out, m, acc, done

    def _tick_decode_spec(self, commit=lambda: None):
        """Speculative sibling of :meth:`_tick_decode`: clamp k, grow
        pages for the verify window, draft, verify once, commit the
        accepted run per lane. Falls back to the plain tick when no lane
        has draft headroom (a lane at cache capacity pins the whole
        tick's k — it is about to retire ``cache_full`` anyway)."""
        lens = {s: int(self.cache_manager.lengths[s]) for s in self._active}
        # write-safety clamp: every lane's verify writes land at
        # lengths..lengths+k, all < cache_len (the per-row update must
        # never clamp-shift into live rows) — so k is the min headroom.
        # A lane can only pin k below spec_k while it sits within k
        # tokens of cache capacity (≤ k ticks before it retires
        # cache_full), and the verify jit caches per distinct k, so the
        # throttle is transient and compiles are bounded by spec_k per
        # engine lifetime.
        k = min(self.spec_k,
                min(self.cache_len - 1 - n for n in lens.values()))
        if k <= 0:
            return self._tick_decode(commit)
        # phase 1: every lane's PENDING-token page first — the exact
        # allocation the plain tick makes, in the same order, so
        # cache_full retirement decisions are identical to the
        # non-speculative engine even under a near-dry pool (draft
        # windows must never starve a neighbor's pending token)
        retired = self._grow_pages(commit)
        if not self._active:
            return retired
        cov = {}
        with span("serving.grow"):
            for slot in sorted(self._active):
                req = self._active[slot]
                # the PR 11-style budget clamp (ISSUE small fix): a draft may
                # never overrun the request's remaining token budget or its
                # page coverage — clamp BEFORE proposing
                budget = max(req.max_new_tokens - len(req.tokens) - 1, 0)
                # phase 2: draft windows from whatever slack remains
                # (uncovered tail writes trash-route; acceptance clamps
                # to the covered span) — and whatever a draft claims
                # here is RETURNED by trim_span after the verify, so the
                # pool a neighbor sees next tick is the plain engine's
                c = self.cache_manager.ensure_span(slot, min(k, budget) + 1)
                cov[slot] = min(k, budget, c - 1)
        req_map = {
            slot: (np.concatenate([req.prompt,
                                   np.asarray(req.tokens, np.int32)]),
                   cov[slot])
            for slot, req in self._active.items()
        }
        with span("serving.draft", batch=len(req_map), k=k):
            # mesh context covers draft-model proposers (their device
            # calls run the same sharded params); the n-gram proposer is
            # pure host and the context is a no-op around it
            with self._mesh_context():
                proposals = self._proposer.propose(req_map, k)
        draft = np.zeros((self.slots, k), np.int32)
        dlen = np.zeros(self.slots, np.int32)
        for slot, (_, cap) in req_map.items():
            d = np.asarray(proposals.get(slot, ()),
                           np.int32).reshape(-1)[:cap]
            draft[slot, :len(d)] = d
            dlen[slot] = len(d)
        if not dlen.any():
            # nothing drafted anywhere (no n-gram match / budgets spent):
            # a k+1-wide verify would emit exactly one token per lane at
            # (k+1)x the cost AND skip the flash-decode fast path — take
            # the plain tick instead (byte-identical for greedy; neither
            # proposer holds per-tick state that needs an observe() here).
            # Phase-2 draft pages go back first, so the plain tick and
            # every neighbor see the plain engine's pool state.
            for slot in sorted(self._active):
                self.cache_manager.trim_span(slot)
            return retired + self._tick_decode(commit)
        all_greedy = all(r.greedy for r in self._active.values())
        active_ids = [r.id for r in self._active.values()]
        attempt = self._fault_ticks
        self._fault_ticks += 1
        # operand binding on the main thread — the same zombie-safety
        # argument as _tick_decode (an abandoned verify call must never
        # see post-recovery buffers)
        cache_in, state_in = self.cache_manager.cache, self._state
        tables_in = self._device_tables()
        draft_dev, dlen_dev = jnp.asarray(draft), jnp.asarray(dlen)

        def run():
            faults.on_serving_tick(attempt)
            faults.on_serving_batch(active_ids)
            out = self._verify_jit(self.params, cache_in, state_in,
                                   tables_in, draft_dev, dlen_dev, k,
                                   all_greedy)
            if self.tick_timeout_s > 0:
                jax.block_until_ready(out)
            return out

        program = self._next_program()
        with span("serving.verify", batch=len(active_ids), k=k,
                  program=program):
            cache, st, out_tok, m, acc, done = self._run_device(run)
        self.cache_manager.cache = cache
        self._state = st
        out_np, m_np, acc_np, done_np = self._fetch(
            "serving.fetch", out_tok, m, acc, done, batch=len(active_ids),
            reads=program, flushed="spec")
        now = self._now()
        proposed = accepted = 0
        emitted_rows = []
        with span("serving.emit", batch=len(active_ids)):
            for slot, req in list(self._active.items()):
                n = int(m_np[slot])
                toks = [int(t) for t in out_np[slot][:n]]
                row_acc = min(int(acc_np[slot]), n)
                proposed += int(dlen[slot])
                accepted += row_acc
                req.spec_proposed += int(dlen[slot])
                req.spec_accepted += row_acc
                emitted_rows.append(n)
                self._delivered += 1
                self.cache_manager.lengths[slot] += n
                # return rejected-draft pages to the pool THIS tick:
                # post-trim the chain matches what the plain engine
                # would hold, so draft windows cost neighbors nothing
                self.cache_manager.trim_span(slot)
                self.metrics.record_tokens(n)
                self._proposer.observe(slot, n)
                finished = bool(done_np[slot])
                failed = False
                for i, t in enumerate(toks):
                    req.tokens.append(t)
                    # firewalled per-token callback, in emission order; a
                    # raise retires THIS request with the tokens streamed so
                    # far — neighbors keep their whole accepted runs
                    if not self._emit_token(req, t, finished and i == n - 1):
                        self._retire_error(req, now)
                        retired.append(req.id)
                        failed = True
                        break
                if failed:
                    continue
                if finished:
                    if (req.eos_token_id >= 0 and toks
                            and toks[-1] == req.eos_token_id):
                        reason = "eos"
                    elif len(req.tokens) >= req.max_new_tokens:
                        reason = "max_length"
                    else:
                        reason = "cache_full"
                    self._finalize(req, reason, now)
                    retired.append(req.id)
        self.metrics.record_spec(proposed, accepted, emitted_rows)
        return retired

    def _emit_token(self, req: Request, tok: int, finished: bool) -> bool:
        """Invoke a request's streaming callback behind a firewall; False
        means the callback raised (the caller retires the request with
        ``finish_reason="error"``)."""
        if not req.on_token:
            return True
        try:
            req.on_token(req.id, tok, finished)
            return True
        except Exception:
            logger.exception(
                "serving: request %d on_token callback raised; retiring it "
                "with finish_reason='error' (other slots unaffected)", req.id)
            return False

    def _retire_error(self, req: Request, now: float) -> None:
        """Retire one request whose callback raised."""
        self._evict(req, "error", now)
        obs_emit("callback_error", request=req.id)

    def _finalize(self, req: Request, reason: str, now: float) -> None:
        if req.slot in self._active and self._active[req.slot] is req:
            del self._active[req.slot]
        if req.slot in self._prefilling and self._prefilling[req.slot] is req:
            del self._prefilling[req.slot]
        if req.slot in self._prefilled and self._prefilled[req.slot] is req:
            del self._prefilled[req.slot]
        req.phase = "finished"  # a mid-prefill retiree's pages and lane
        # free below (no leak)
        if req.slot is not None:  # queued-expiry/cancel never held a slot
            if self._proposer is not None:
                self._proposer.on_retire(req.slot)
            self.cache_manager.free(req.slot)
        self.metrics.record_retire(now - req.submit_time, reason)
        self._results[req.id] = ServingResult(
            id=req.id, prompt=req.prompt,
            tokens=np.asarray(req.tokens, np.int32), finish_reason=reason,
            ttft_s=(req.first_token_time or now) - req.submit_time,
            latency_s=now - req.submit_time,
        )

    @functools.cached_property
    def _plan(self) -> dict:
        """Span fields of every prefill call and tick that are constants of
        the model's plan of layers (``mixed_stack.mover_layers``): none for
        a model without layer types. (At the file's end: no line above it
        moves, so no program's compile-cache key does.)"""
        from fleetx_tpu.models.gpt.mixed_stack import mover_layers

        return mover_layers(self.model.cfg)


def _by_head_leaves(params) -> list:
    """The leaves of the served tree that are held heads-major
    (``models/gpt/resident.py``; none in a tree of another family). At the
    file's end, like ``_plan``: no line above it moves."""
    from fleetx_tpu.models.gpt.resident import by_head_leaves

    return by_head_leaves(params)
