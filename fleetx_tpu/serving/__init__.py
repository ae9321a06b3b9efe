"""Continuous-batching serving subsystem over the flash-decode fast path.

The runtime layer the reference toolkit never had: instead of one padded
batch per blocking ``generate()`` call, a slot-based scheduler keeps the
decode batch full — requests are admitted into free decode lanes the
tick they arrive (prefill-on-insert), every tick runs ONE jitted decode
step over all lanes at their own depths, and finished requests free
their lane immediately for the next queued request.

K/V storage is PAGED: a shared ``[num_pages, page_size, ...]`` pool with
per-request block tables and a refcounted prefix trie, so cache capacity
tracks live tokens (page-granular admission) and requests sharing a
system prompt reuse one prefill.

    engine = ServingEngine(model, variables, slots=8)
    rid = engine.submit(prompt_ids, max_length=64)
    results = engine.drain()          # {rid: ServingResult}

Layout: ``cache_manager`` (page pool + prefix trie, and the no-zeroing
live-window safety argument), ``scheduler`` (FIFO
admission policy seam), ``engine`` (submit/step/drain loop + jitted
prefill/decode), ``model_protocol`` (the model-agnostic serving
contract: executor seam + capability flags + the router-facing engine
surface), ``batch_engine`` / ``ernie_engine`` / ``embedding_engine``
(KV-free dynamic-batching engines for encoder-style models), ``metrics``
(queue/TTFT/throughput/prefix-reuse observability), ``router``
(N-replica dispatch with per-model groups, health-based failover,
zero-token-loss migration, and per-tenant QoS: DRR weighted-fair lanes,
admission budgets, priority preemption), ``autoscaler`` (closed-loop
fleet sizing off replica health with prefix pre-warm), ``workload``
(seeded trace generation — Poisson or heavy-tailed Azure-LLM-shaped —
+ the SLO goodput scorer). docs/SERVING.md has the architecture tour.
"""

from fleetx_tpu.serving.autoscaler import FleetAutoscaler

from fleetx_tpu.serving.cache_manager import (
    DiskPageStore,
    HostPageStore,
    PagedKVCacheManager,
    PagePool,
    TieredPageStore,
)
from fleetx_tpu.serving.embedding_engine import (
    EmbeddingEngine,
    decode_floats,
    encode_floats,
)
from fleetx_tpu.serving.engine import (
    QueueFull,
    RecoveryExhausted,
    ServingEngine,
    ServingResult,
    ShuttingDown,
    TickTimeout,
    sample_tokens,
)
from fleetx_tpu.serving.ernie_engine import ErnieScoringEngine
from fleetx_tpu.serving.batch_engine import BatchingEngine
from fleetx_tpu.serving.metrics import ServingMetrics
from fleetx_tpu.serving.model_protocol import (
    ENGINE_SURFACE,
    GPTExecutor,
    ModelCapabilities,
    ModelExecutor,
    engine_conforms,
)
from fleetx_tpu.serving.router import (
    ReplicaState,
    RouterMetrics,
    ServingRouter,
    TenantPolicy,
)
from fleetx_tpu.serving.scheduler import FIFOScheduler, Request
from fleetx_tpu.serving.spec import (
    DraftModelProposer,
    NgramProposer,
    Proposer,
)
from fleetx_tpu.serving.workload import (
    DISTRIBUTIONS,
    RequestOutcome,
    TenantSpec,
    TraceDistribution,
    TraceRequest,
    WorkloadSpec,
    generate_trace,
    run_trace,
    score_goodput,
    trace_hash,
)

__all__ = [
    "QueueFull",
    "RecoveryExhausted",
    "ServingEngine",
    "ServingResult",
    "ShuttingDown",
    "TickTimeout",
    "BatchingEngine",
    "EmbeddingEngine",
    "ErnieScoringEngine",
    "ENGINE_SURFACE",
    "GPTExecutor",
    "ModelCapabilities",
    "ModelExecutor",
    "engine_conforms",
    "decode_floats",
    "encode_floats",
    "DiskPageStore",
    "HostPageStore",
    "PagePool",
    "PagedKVCacheManager",
    "TieredPageStore",
    "FIFOScheduler",
    "Request",
    "DraftModelProposer",
    "NgramProposer",
    "Proposer",
    "DISTRIBUTIONS",
    "FleetAutoscaler",
    "ReplicaState",
    "RequestOutcome",
    "RouterMetrics",
    "ServingMetrics",
    "ServingRouter",
    "TenantPolicy",
    "TenantSpec",
    "TraceDistribution",
    "TraceRequest",
    "WorkloadSpec",
    "generate_trace",
    "run_trace",
    "sample_tokens",
    "score_goodput",
    "trace_hash",
]
