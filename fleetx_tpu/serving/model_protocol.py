"""The model-agnostic serving protocol: what a model must provide to be
served, and what an engine must provide to be routed.

The serving stack grew GPT-shaped end to end (PRs 2–17): the engine
called ``init_decode_cache`` / ``decode_step`` directly, the router
assumed every replica decodes autoregressively, and the API layer
reported one hardcoded model. The source paper's premise is a ONE-STOP
toolkit — GPT, ERNIE, ViT, MoCo — so this module factors the two
implicit contracts into explicit ones:

**The model-side contract** (:class:`ModelExecutor`): the four seams
``ServingEngine`` actually needs from a model — init cache, the
bucketed prefill / decode forward, and per-row sampling — plus
:class:`ModelCapabilities` flags that say which engine features the
model can legally ride (KV cache, speculative decoding, cache layout).
:class:`GPTExecutor` is the existing GPT path behind that interface:
every method delegates to the exact functions the engine called before
the extraction (``fleetx_tpu/models/gpt/generation.py`` +
``serving/engine.py``'s shared sampler), so the refactor is provably
behavior-free — the byte-parity suites run unchanged.

**The engine-side contract** (:data:`ENGINE_SURFACE`): the
submit/step/healthz surface ``ServingRouter`` and ``ApiServer`` consume.
Three engine kinds implement it today — the autoregressive
``ServingEngine`` (GPT), the encoder-style ``ErnieScoringEngine``
(fill-in-blank / sentence-order scoring; no decode loop), and the
KV-free ``EmbeddingEngine`` (ViT/MoCo dynamic batching; no cache at
all). ``tests/test_protocol.py`` runs one conformance suite against all
three; :func:`engine_conforms` is the structural check it (and the
router, defensively) uses.

Capability flags ride the ``/healthz`` report (``model`` +
``capabilities`` keys), which is how a cross-process router learns what
each replica serves without importing its model code — the same
scrape-don't-import discipline as the ``role`` field
(docs/SERVING.md "Heterogeneous fleet").
"""

from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Optional

import jax
import numpy as np

__all__ = [
    "ENGINE_SURFACE",
    "GPTExecutor",
    "ModelCapabilities",
    "ModelExecutor",
    "device_counters_of",
    "engine_conforms",
]


@dataclasses.dataclass(frozen=True)
class ModelCapabilities:
    """What engine features a served model family can legally ride.

    The flags gate features at CONSTRUCTION, not mid-request: an engine
    asked to speculate over a model whose executor says
    ``supports_spec=False`` must refuse up front with a cause, the same
    fail-at-the-seam discipline as the mesh validation."""

    #: model family name — the router's grouping key and the id prefix
    #: the API layer lists in ``/v1/models`` ("gpt" | "ernie" | "vit" ...)
    family: str
    #: the model decodes autoregressively against a KV cache; False means
    #: the engine owns no cache pool and every request is one forward
    has_kv_cache: bool
    #: draft-and-verify speculative decoding is legal (requires a decode
    #: loop whose verify call replays multi-token windows — GPT only)
    supports_spec: bool
    #: "paged" (the GPT engine's page pool), "none" (KV-free)
    cache_layout: str
    #: hard per-request input bound (tokens for text, flat elements for
    #: vision) — what the router's per-group submit validation prices
    max_input: int
    #: what the int32 output channel carries: "tokens" (real token /
    #: class ids) or "floats" (a float32 vector bit-cast losslessly —
    #: serving/embedding_engine.py's wire encoding). The API layer keys
    #: ``/v1/embeddings`` eligibility on this, not on KV-freeness —
    #: ERNIE is KV-free but token-out
    emits: str = "tokens"
    #: int8 weights (weight-only PTQ), an int8 KV cache and a serving mesh
    #: are each legal only where a test covers them for this model; the
    #: engine refuses the others at construction
    supports_int8_weights: bool = True
    supports_int8_kv: bool = True
    supports_mesh: bool = True
    #: the classes of page the engine's pool keeps for the model: "full"
    #: (a layer keeps every token of a lane) and, for a model with
    #: window-attention layers, "window" (a layer keeps what a live query
    #: can still see: serving/cache_manager.py "Two classes of page").
    #: Window pages are released behind the window, so a prompt's prefix
    #: cannot be reused from them, nor shipped between replicas. A model
    #: with EVA attention keeps ("summary", "window") in EVERY layer: one
    #: pooled row a chunk of positions, and the exact rows of a lane's
    #: current TUMBLING window ("EVA's two classes")
    page_classes: tuple = ("full",)
    supports_prefix_cache: bool = True
    supports_roles: bool = True
    #: the kinds of state a lane keeps: "kv" (keys and values of attention
    #: layers, in the page pool); for a model with gated short-convolution
    #: layers, "conv" (the operator's last inputs, in tail pages under the
    #: same block table: models/gpt/mixed_stack.py): a page's tail rows
    #: live and die with the page, so a prefix hit resumes them; for a
    #: model with selective-scan layers, "ssm" (the scan's state and its
    #: filter's last inputs, held ONCE A LANE outside the pool and updated
    #: in place): no page holds it, so a prefix hit has nothing to resume
    #: and prefix reuse is refused; for a model with delta-rule linear
    #: attention layers, "kda" (the rule's matrix state a head and its
    #: three filters' last inputs: the same home, the same refusals; which
    #: kind is lane-resident is ``cfg.lane_state``'s to say). No recurrent
    #: kind is spilled to the host tiers or shipped between replicas
    state_kinds: tuple = ("kv",)
    supports_host_spill: bool = True
    #: rows that are not tokens: the model takes ``[rows, hidden]`` rows of
    #: a vision tower beside ids (``submit(prompt, images=...)``; the
    #: engine then runs the tower, keys the prefix trie by a row's key and
    #: hands every prefill program its rows: docs/SERVING.md "Rows from a
    #: tower"). ``mrope``: its rotary positions have three axes and are not
    #: the cache row, so a lane carries ``rope_delta`` and every program
    #: takes positions ``[3, rows]`` ("Positions apart from rows"). A model
    #: without them never sees either operand
    takes_rows: bool = False
    mrope: bool = False

    def as_dict(self) -> dict:
        """JSON-ready form for the ``/healthz`` report (a tuple reads as
        the list its JSON round trip gives back)."""
        return {k: list(v) if isinstance(v, tuple) else v
                for k, v in dataclasses.asdict(self).items()}

    def require(self, **asked) -> None:
        """The construction-time gate: ``asked`` maps a flag of this
        class to whether the engine was asked for that feature; the first
        one asked for and not supported raises ``ValueError`` with the
        family and the flag in it."""
        for flag, wanted in asked.items():
            if wanted and not getattr(self, flag):
                raise ValueError(
                    f"model family {self.family!r} does not support "
                    f"{_FEATURES[flag]} (capabilities.{flag}=False)")


_FEATURES = {
    "has_kv_cache": "a KV cache: serve it behind a KV-free engine "
                    "(serving/batch_engine.py), not ServingEngine",
    "supports_spec": "speculative decoding",
    "supports_int8_weights": "int8 weights: no test covers them",
    "supports_int8_kv": "an int8 KV cache: no test covers it",
    "supports_mesh": "a serving mesh: no test covers it",
    "supports_prefix_cache": "prefix reuse: its window-attention layers "
                             "(or its EVA layers' tumbling window) "
                             "release a prefix's pages once the window has "
                             "passed them, or its selective-scan or "
                             "delta-rule layers keep "
                             "their state once a lane, with no snapshot at "
                             "the match's end to resume from",
    "supports_roles": "a prefill or decode role: the pages of its window "
                      "class, the tail pages of its convolution state, the "
                      "lane-resident state of its selective-scan or "
                      "delta-rule layers, the pages of a flat pool over "
                      "mixed layers, or the pooled rows of its EVA layers' "
                      "summary class, are not shipped between replicas",
    "supports_host_spill": "a host or disk page tier: the tail pages of its "
                           "convolution state, the lane-resident state "
                           "of its selective-scan or delta-rule layers, "
                           "the pages of a flat pool over mixed layers, or "
                           "the pooled rows of its EVA layers' summary "
                           "class, are not spilled",
}


class ModelExecutor:
    """The model-side serving contract (abstract).

    ``ServingEngine`` consumes ONLY this surface for model compute: a
    fresh cache (:meth:`init_cache`), the cached forward that serves
    both bucketed prefill and the decode tick (:meth:`forward`), and
    the shared per-row sampling pipeline (:meth:`sample` /
    :meth:`filter`). Encoder-style engines (ERNIE, ViT) do not run a
    decode loop and need none of this — they call their model directly
    — but still advertise :attr:`capabilities` so the router and
    ``/healthz`` treat every replica uniformly.

    All methods are traced under ``jax.jit``: implementations must be
    pure functions of their arguments (plus the model closed over at
    construction)."""

    capabilities: ModelCapabilities

    def bind(self, model):
        """Rebind to a decode-configured model clone. The engine patches
        cache length / page layout onto ``model.cfg`` before tracing
        anything; executors built over the raw model get this call with
        the clone so :meth:`init_cache` / :meth:`forward` read the
        serving cache config, not the training one."""
        raise NotImplementedError

    def resident_params(self, params):
        """The tree the engine keeps on the device for its programs, from
        the servable tree it was handed: what the model would convert in
        every program, converted once. Called once, at construction,
        before the tree is sharded. The tree as handed over by default."""
        return params

    def init_cache(self, batch: int):
        """A fresh decode cache for ``batch`` lanes (None when
        ``capabilities.has_kv_cache`` is False)."""
        raise NotImplementedError

    def forward(self, params, cache, ids, positions, mask=None, *,
                cache_positions=None, block_tables=None, logit_rows=None):
        """One cached forward: ``(logits, new_cache)``. Serves bucketed
        prefill (multi-token ``ids``) and the decode tick (one token per
        lane) through the same seam; ``cache_positions`` are per-lane
        write offsets, ``block_tables`` the paged indirection.

        ``logit_rows`` says which rows' logits the caller will read, and
        the head runs on those alone. None (the default) is every row:
        ``[b, rows, vocab]``, what the tick (one row a lane) and the
        speculative verify call (every proposed row) read. An int32
        ``[b]`` is ONE row a batch element: ``[b, 1, vocab]``, the row
        sliced from the backbone's output before the head's product, so
        no ``[rows, vocab]`` array exists (a prefill samples from its
        last true row). A negative entry asks for no row: where every
        entry is negative the head is not run (an intermediate chunk, a
        replay) and the logits returned are not to be read. The cache
        written is the same whatever the rows.

        Implementations of a ``takes_rows`` family also take
        ``input_rows=(rows [b, s, hidden], is_image [b, s])``: the rows
        that enter the stack where ``is_image`` marks them, in the place of
        the word table's."""
        raise NotImplementedError

    def sample(self, logits, keys, greedy, temperature, top_k, top_p, *,
               topk_cap: int):
        """Per-row sampling: each row applies its own strategy knobs and
        draws from its own rng key; returns int32 tokens."""
        raise NotImplementedError

    def filter(self, logits, temperature, top_k, top_p, *, topk_cap: int):
        """The sampling filter pipeline alone (speculative verification
        needs the filtered distribution, not a draw)."""
        raise NotImplementedError

    def counters(self, cache) -> dict:
        """Numbers the model's programs accumulated ON THE DEVICE inside
        the cache tree they carry (an expert model's routing counts), as
        a flat dict for ``ServingMetrics.snapshot()``. This is the one
        place they are fetched: no tick reads them. Empty by default."""
        return {}


class GPTExecutor(ModelExecutor):
    """The decoder stack of ``models/gpt/model.py`` behind the protocol —
    pure delegation: every block ``GPTConfig`` describes (the GPT-2 block,
    the rotary / RMSNorm / gated block, softmax top-k experts) is served
    by the same cached forward, under the family name its configuration
    gives (``cfg.family``: "gpt", "olmoe").

    Every method forwards to the exact function the engine called
    before the extraction, with the model closed over; tracing under
    ``jit`` produces identical programs, which is what keeps the
    byte-parity suites green unchanged.

    The capability flags say what was really tried: over an expert layer
    no test covers speculation (a verify call routes several tokens of a
    lane at once), int8 weights (``ops/quant.py`` knows no expert axis),
    an int8 cache or a mesh (the grouped-matmul kernels are not sharded),
    so an engine asked for one of those over experts refuses at
    construction."""

    def __init__(self, model, family: Optional[str] = None):
        self.model = model
        dense = not getattr(model.cfg, "expert_mode", False)
        # grouped heads, a head size of its own, layers of two kinds
        # (models/gpt/hybrid.py): the decode kernels take grouped heads
        # without int8 scales and without a mesh, and no test speculates
        kinds = bool(getattr(model.cfg, "layer_kinds", False))
        windowed = kinds and any(model.cfg.window_layers)
        dense = dense and not kinds
        state = tuple(getattr(model.cfg, "state_kinds", ("kv",)))
        recurrent = state != ("kv",)
        # a ``layer_types`` stack keeps ONE flat pool over its layers
        # (hybrid.init_cache): a page's payload, as the spill tiers and the
        # page ship read it, would be one layer's. The stack of
        # full-attention layers under an indexer (three leaves) is the
        # first that could ask for either with its trie on, and is refused
        # both; every other family keeps the flags it had (ROADMAP R9)
        flat = bool(getattr(model.cfg, "layer_types", None)) and bool(
            getattr(model.cfg, "indexed", False))
        # EVA attention (models/gpt/eva.py): a summary and a tumbling window
        # class in every layer; what the window class refuses it refuses,
        # and its pooled rows are neither spilled nor shipped
        eva = bool(getattr(model.cfg, "eva", False))
        windowed, flat = windowed or eva, flat or eva
        self.capabilities = ModelCapabilities(
            family=family or getattr(model.cfg, "family", "gpt"),
            has_kv_cache=True,
            supports_spec=dense,
            cache_layout="paged",
            max_input=int(model.cfg.max_position_embeddings),
            supports_int8_weights=dense,
            supports_int8_kv=dense,
            supports_mesh=dense,
            page_classes=(("summary", "window") if eva else
                          ("full", "window") if windowed else ("full",)),
            supports_prefix_cache=not windowed and not getattr(
                model.cfg, "lane_state", ("", ()))[0],
            supports_roles=not windowed and not recurrent and not flat,
            state_kinds=state,
            supports_host_spill=not recurrent and not flat,
            takes_rows=bool(getattr(model.cfg, "vision", None)),
            mrope=bool(getattr(model.cfg, "mrope_section", None)),
        )

    def bind(self, model):
        return GPTExecutor(model, family=self.capabilities.family)

    def counters(self, cache) -> dict:
        """The routing counts of ``parallel/moe.py`` ``DroplessMoEMLP``
        (its ``moe_stats`` cache leaves, per layer :data:`MOE_STATS` as
        two-word counts): per layer and per program the experts that had
        a row, the largest-over-mean expert load and the tiles of rows
        the grouped matmuls walked against the tiles their static layout
        laid, for one-token programs (ticks) and longer ones (prefills),
        and the token-expert pairs routed in all."""
        from fleetx_tpu.parallel.moe import MOE_STATS

        leaves = [leaf for path, leaf in
                  jax.tree_util.tree_flatten_with_path(cache)[0]
                  if "moe_stats" in jax.tree_util.keystr(path)]
        if not leaves:
            return {}
        base = 2 * len(MOE_STATS) * 2      # (a leaf's first words; the rest
        # are the zero-compute experts' and a held group's,
        # parallel/moe_share.py)
        leaves = [np.asarray(leaf).reshape(-1, leaf.shape[-1])
                  for leaf in leaves]
        words = np.concatenate([leaf[:, :base].astype(np.uint64).reshape(
            -1, 2, len(MOE_STATS), 2) for leaf in leaves])
        per_layer = words[..., 0] + (words[..., 1] << np.uint64(32))
        stats = per_layer.sum(axis=0)            # [ticks | prefills, stat]
        experts = int(self.model.cfg.num_experts)
        out = {"moe_layers": len(per_layer),
               "moe_pairs_routed": int(stats[:, 1].sum())}
        for kind, (calls, pairs, read, largest, walked, laid) in zip(
                ("tick", "prefill"), stats.tolist()):
            out[f"moe_{kind}_layer_calls"] = calls
            out[f"moe_{kind}_pairs"] = pairs
            out[f"moe_{kind}_experts_read"] = read / calls if calls else 0.0
            # (a layer and program, mean: walked / laid is the share of
            # the static grid that the traffic fills)
            out[f"moe_{kind}_tiles_walked"] = walked / calls if calls else 0.0
            out[f"moe_{kind}_tiles_laid"] = laid / calls if calls else 0.0
            # the largest expert's rows over the mean expert's, over all
            # the calls (each weighted by the pairs it routed)
            out[f"moe_{kind}_load_max_over_mean"] = (
                largest * experts / pairs if pairs else 0.0)
        if leaves[0].shape[1] > base:
            from fleetx_tpu.parallel.moe_share import extra_counters

            out.update(extra_counters(
                self.model.cfg,
                np.concatenate([leaf[:, base:] for leaf in leaves])))
        return out

    def resident_params(self, params):
        from fleetx_tpu.models.gpt.resident import resident_params

        return resident_params(self.model.cfg, params)

    def init_cache(self, batch: int):
        from fleetx_tpu.models.gpt.generation import init_decode_cache

        return init_decode_cache(self.model, batch)

    def forward(self, params, cache, ids, positions, mask=None, *,
                cache_positions=None, block_tables=None, logit_rows=None,
                **rows_in):
        import jax

        from fleetx_tpu.models.gpt.generation import decode_step
        from fleetx_tpu.models.gpt.head import row_logits_step

        # device-trace scope: under it the layer scan's own slices and
        # updates move the KV cache (docs/OBSERVABILITY.md, parts)
        with jax.named_scope("cached_forward"):
            step = decode_step if logit_rows is None else functools.partial(
                row_logits_step, logit_rows=logit_rows)  # body and head apart
            logits, cache = step(
                self.model, params, cache, ids, positions, mask,
                cache_positions=cache_positions, block_tables=block_tables,
                **rows_in)
        if getattr(self.model.cfg, "num_pred_heads", 1) > 1:
            # of several prediction heads the FIRST is served (the next
            # token); the model computes them all (models/gpt/head.py)
            logits = logits[..., :self.model.cfg.vocab_size]
        return logits, cache

    def sample(self, logits, keys, greedy, temperature, top_k, top_p, *,
               topk_cap: int):
        from fleetx_tpu.serving.engine import sample_tokens

        return sample_tokens(logits, keys, greedy, temperature, top_k,
                             top_p, topk_cap=topk_cap)

    def filter(self, logits, temperature, top_k, top_p, *, topk_cap: int):
        from fleetx_tpu.serving.engine import filter_logits

        return filter_logits(logits, temperature, top_k, top_p,
                             topk_cap=topk_cap)


def device_counters_of(engine):
    """``() -> dict`` for ``ServingMetrics.device_counters``: what the
    engine's executor reads from the cache tree its programs carry
    (:meth:`ModelExecutor.counters`). Called by ``snapshot()`` alone, so a
    tick fetches nothing; holds the engine weakly. The engine's tick in
    flight is read first, so that what the host counted (tokens,
    retirements, positions) and what the device counted describe the same
    ticks: a ``snapshot()`` that reads the device belongs to the thread
    that drives the engine."""
    from fleetx_tpu.obs.events import emit as obs_emit

    ref = weakref.ref(engine)

    def read() -> dict:
        eng = ref()
        if eng is None:
            return {}
        eng._settle("other")
        try:
            # and the pool by class of page, which is host state, as is
            # start-up's work count: the buckets whose prefill program the
            # engine has minted (each traced and compiled, or loaded, once)
            return {**eng.executor.counters(eng.cache_manager.cache),
                    **eng.cache_manager.class_counters(),
                    "prefill_programs": len(eng._prefill_jits)}
        except RuntimeError as err:
            # a scrape from another thread met a cache buffer the running
            # tick had just donated: nothing to report now, and the event
            # log says it happened
            if "deleted" not in str(err):
                raise
            obs_emit("serving_device_counters_missed",
                     engine=eng.metrics.engine_label)
            return {}

    return read


#: The engine-side contract: every serving engine kind — autoregressive
#: or not — exposes this surface, and the router/API layers consume
#: NOTHING else. Methods: the names below; attributes: ``role``
#: ("prefill"/"decode"/"both"), ``paged`` (bool), ``page_size``,
#: ``cache_len``, ``slots``, ``model`` (with ``.cfg``), ``metrics``
#: (``ServingMetrics``-shaped), ``capabilities``
#: (:class:`ModelCapabilities`), ``model_family`` (str), and
#: ``submit_limit`` (the smallest REJECTED per-request input size — the
#: router's per-group admission bound). ``health()`` returns the
#: ``/healthz`` JSON body: ``state`` ok/draining/dead, ``role``,
#: ``model``, ``capabilities``, ``queue_depth``, ``queue_tokens``,
#: ``active``, ``slots``.
ENGINE_SURFACE = (
    "submit", "step", "take_result", "result", "cancel", "emitted_tokens",
    "health", "drain", "shutdown", "request_shutdown", "declare_dead",
)

_ENGINE_ATTRS = ("role", "paged", "page_size", "cache_len", "slots",
                 "model", "metrics", "capabilities", "model_family",
                 "submit_limit")


def engine_conforms(engine, *, require_attrs: bool = True
                    ) -> Optional[str]:
    """Structural conformance check against :data:`ENGINE_SURFACE`:
    returns None when ``engine`` exposes the full router-facing
    contract, else the first missing member's name (the conformance
    tests and the router's construction-time validation both report
    it)."""
    for name in ENGINE_SURFACE:
        if not callable(getattr(engine, name, None)):
            return name
    if require_attrs:
        for name in _ENGINE_ATTRS:
            if not hasattr(engine, name):
                return name
    return None
