"""Speculative-decoding proposers for the serving engine.

Speculative decoding (docs/SERVING.md "Speculative decoding";
Leviathan et al., "Fast Inference from Transformers via Speculative
Decoding") splits each decode tick into DRAFT and VERIFY: a cheap
proposer guesses up to ``k`` next tokens per active request, the engine
writes them into the request's pages and scores all ``k+1`` positions
with ONE batched prefill-shaped call, and acceptance keeps the longest
prefix the target model agrees with — greedy outputs are byte-identical
to the non-speculative engine by construction, sampling outputs are
distribution-preserving via standard speculative rejection.

This module owns the PROPOSER side of that split, behind one small
protocol (:class:`Proposer`) so operators can plug their own:

- :class:`NgramProposer` (the default): host-side prompt-lookup / n-gram
  drafting — match the request's trailing n-gram against its own
  ``prompt + generated`` history and propose the tokens that followed
  the previous occurrence. Zero extra device memory or compute; shines
  exactly on the shared-system-prompt, code-edit, and
  retrieval-grounded workloads this repo's serving stack optimizes for
  (the continuation is literally in the context).
- :class:`DraftModelProposer`: a small GPT drafts ``k`` greedy tokens
  per tick through its OWN decode lanes (a private slot-layout KV cache
  sized ``[slots, cache_len]`` for the draft model's dims — the main
  page pool's page shapes are the target model's, so the draft keeps a
  sibling cache rather than aliasing those pages). It rides the same
  decode seams as the engine: ``decode_step`` with per-row
  ``cache_positions``, bucketed multi-token catch-up prefills, and the
  int8 weight-only dequant-in-jit machinery when handed a quantized
  tree. Draft-lane rollback is the same host-side pointer move the
  engine uses — rejected draft KV beyond the live window is never
  attended, so a mis-predicted tail costs nothing.

A proposer can NEVER affect correctness — verification gates every
token — only the acceptance rate (and therefore the speedup). That is
why the draft cache needs no crash-safety machinery of its own:
``reset()`` simply zeroes the lane pointers and the next ``propose()``
re-prefills lazily from host truth (the engine calls it from
``recover()``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fleetx_tpu.serving.cache_manager import KV_LEAF_RANK

__all__ = ["DraftModelProposer", "NgramProposer", "Proposer",
           "build_proposer"]

# slot -> (prompt + generated history, max draft tokens wanted this tick)
SpecRequests = Dict[int, Tuple[np.ndarray, int]]


class Proposer(Protocol):
    """The draft side of speculative decoding (module docstring).

    The engine drives one proposer per tick: ``propose()`` over the
    active lanes, ``observe()`` after verification tells each lane how
    many tokens were actually emitted (so stateful proposers rewind
    their rejected tails), ``on_retire()`` frees a lane, ``reset()``
    drops all lane state after an engine recovery (the next
    ``propose()`` rebuilds lazily from the histories the engine passes
    — which are host truth, so recovery stays byte-identical).
    Proposals are suggestions only: verification gates every token, so
    a proposer bug can cost acceptance rate, never correctness."""

    name: str

    def bind(self, slots: int, cache_len: int) -> None:
        """Size per-lane state for ``slots`` decode lanes."""
        ...

    def propose(self, requests: SpecRequests, k: int
                ) -> Dict[int, np.ndarray]:
        """Draft up to ``min(k, cap)`` tokens per requested lane; lanes
        may be omitted from the result (no draft this tick)."""
        ...

    def observe(self, slot: int, emitted: int) -> None:
        """Verification emitted ``emitted`` tokens for ``slot``."""
        ...

    def on_retire(self, slot: int) -> None:
        """The request holding ``slot`` retired; free its lane state."""
        ...

    def reset(self) -> None:
        """Drop all lane state (engine recovery rebuilt the device)."""
        ...


class NgramProposer:
    """Prompt-lookup drafting: propose the continuation of the most
    recent earlier occurrence of the request's trailing n-gram inside
    its own ``prompt + generated`` history (longest ``n`` in
    ``[min_n, max_n]`` wins). Pure host state-free string matching —
    zero device memory, zero extra model FLOPs — and exactly the
    drafting mode that wins on repetitive / template / retrieval
    contexts where the continuation already appears verbatim."""

    name = "ngram"

    def __init__(self, max_n: int = 3, min_n: int = 1):
        if not 1 <= min_n <= max_n:
            raise ValueError(
                f"need 1 <= min_n <= max_n, got ({min_n}, {max_n})")
        self.max_n = max_n
        self.min_n = min_n

    def bind(self, slots: int, cache_len: int) -> None:
        """Stateless — nothing to size."""

    def propose(self, requests: SpecRequests, k: int
                ) -> Dict[int, np.ndarray]:
        """Suffix-match each lane's history; omit lanes with no match."""
        out = {}
        for slot, (hist, cap) in requests.items():
            if cap <= 0:
                continue
            d = self._match(np.asarray(hist, np.int64), min(cap, k))
            if d.size:
                out[slot] = d
        return out

    def _match(self, hist: np.ndarray, cap: int) -> np.ndarray:
        """Tokens that followed the most recent earlier occurrence of
        the trailing n-gram (longest n first); empty when none recurs."""
        size = len(hist)
        for n in range(self.max_n, self.min_n - 1, -1):
            if size <= n:
                continue
            pattern = hist[size - n:]
            windows = np.lib.stride_tricks.sliding_window_view(hist, n)
            # candidate starts: every position but the pattern's own
            hits = np.nonzero(
                (windows[:size - n] == pattern).all(axis=1))[0]
            if hits.size:
                start = int(hits[-1]) + n
                return hist[start:start + cap].astype(np.int32)
        return np.empty(0, np.int32)

    def observe(self, slot: int, emitted: int) -> None:
        """Stateless — the next propose() re-reads the history."""

    def on_retire(self, slot: int) -> None:
        """Stateless — nothing held per lane."""

    def reset(self) -> None:
        """Stateless — nothing to drop."""


def scatter_slot(cache, prefill_cache, slot):
    """Write a 1-row cache tree into row ``slot`` of the draft model's
    slot-layout cache (its only user: the serving engine itself holds
    pages).

    Pure function (``slot`` may be traced). K/V leaves carry a
    ``[..., batch, cache_len, lanes]`` suffix (``KV_LEAF_RANK``) — the
    batch axis sits at -3 for both the scan-stacked
    ``[layers, batch, ...]`` and the unrolled nested layouts — and are
    updated at that axis; lower-rank leaves (the ``cache_index``
    scalars) are left untouched, since per-lane progress is tracked by
    the proposer."""

    def put(big, small):
        if big.ndim < KV_LEAF_RANK:
            return big
        starts = ((0,) * (big.ndim - KV_LEAF_RANK) + (slot,)
                  + (0,) * (KV_LEAF_RANK - 1))
        return jax.lax.dynamic_update_slice(big, small, starts)

    return jax.tree.map(put, cache, prefill_cache)


def _gather_slot(cache, slot):
    """Slice one lane's row out of a slot-layout cache tree (the inverse
    of :func:`scatter_slot`): K/V leaves keep their
    ``[..., batch, cache_len, lanes]`` suffix (``KV_LEAF_RANK``) with the
    batch axis cut to 1; lower-rank leaves (the ``cache_index`` scalars)
    pass through untouched."""

    def take(big):
        if big.ndim < KV_LEAF_RANK:
            return big
        ax = big.ndim - KV_LEAF_RANK
        starts = (0,) * ax + (slot,) + (0,) * (KV_LEAF_RANK - 1)
        sizes = big.shape[:ax] + (1,) + big.shape[ax + 1:]
        return jax.lax.dynamic_slice(big, starts, sizes)

    return jax.tree.map(take, cache)


class DraftModelProposer:
    """Draft-model speculative decoding: a small GPT predicts ``k``
    greedy tokens per active lane each tick (module docstring).

    Per-lane state is exactly the engine's: a slot-layout decode cache
    ``[slots, cache_len]`` for the DRAFT model's dims, a host
    ``lengths`` mirror (KV valid over ``[0, lengths)``), and the last
    emitted token. The sync protocol is catch-up-then-draft:
    ``propose()`` first prefills any history the draft cache is missing
    (a fresh admission's whole prompt; the single token a
    fully-accepted tick leaves behind; everything after a
    ``reset()``) through bucketed multi-token ``decode_step`` calls at
    the lane's absolute positions, then runs ``k`` batched single-token
    greedy steps — the draft KV for accepted tokens is already in place
    for the next tick, and ``observe()`` rewinds the live length past
    the rejected tail (host pointer move; stale KV beyond the window is
    never attended — the engine's own no-zeroing contract).

    Handed an int8 weight-only tree (``{"_q8", "_scale"}`` leaves, e.g.
    the engine's own params under ``FLEETX_SERVING_SPEC_DRAFT=self``
    with ``FLEETX_SERVING_WEIGHT_DTYPE=int8``), every jitted call
    dequantizes in-jit exactly like the engine's — the draft rides the
    same quantization machinery."""

    name = "draft"

    def __init__(self, model, variables, prefill_bucket: int = 32):
        self._base_model = model
        v = variables
        self.params = (v["params"]
                       if isinstance(v, dict) and "params" in v else v)
        self.prefill_bucket = max(int(prefill_bucket), 1)
        self.model = None  # sized at bind()

    def bind(self, slots: int, cache_len: int) -> None:
        """Clone the draft model onto a private slot-layout decode cache
        (no pages, no kv quantization — the draft cache is small and
        its contents are only ever suggestions)."""
        from fleetx_tpu.models.gpt.generation import init_decode_cache

        self.model = self._base_model.clone(cfg=dataclasses.replace(
            self._base_model.cfg, decode_cache_len=cache_len,
            decode_num_pages=None, decode_page_size=None,
            decode_kv_dtype=None))
        self.slots = slots
        self.cache_len = cache_len
        self.cache = init_decode_cache(self.model, slots)
        self.lengths = np.zeros(slots, np.int64)
        self.last_tok = np.zeros(slots, np.int32)
        self._written: Dict[int, int] = {}  # lane -> draft KV positions
        self._step_jit = jax.jit(self._step_fn)
        self._catchup_jits = {}

    def _dequant(self, params):
        """In-jit dequant seam: ``dequantize_tree_int8`` expands
        ``{"_q8", "_scale"}`` leaves and passes float leaves through
        untouched (a free identity on unquantized trees inside jit),
        so the one call handles both — no separate detection to drift
        from ops/quant's leaf format."""
        from fleetx_tpu.ops.quant import dequantize_tree_int8

        return dequantize_tree_int8(params, dtype=jnp.float32)

    def _step_fn(self, params, cache, last_tok, lengths, active):
        """One batched greedy draft token for every lane (inactive lanes
        ride along pinned to the last cache row, outputs discarded —
        the engine's decode-tick pattern)."""
        params = self._dequant(params)
        max_pos = self.model.cfg.max_position_embeddings
        wpos = jnp.where(active, lengths, self.cache_len - 1)
        posid = jnp.where(active, jnp.minimum(lengths, max_pos - 1), 0)
        from fleetx_tpu.models.gpt.generation import decode_step

        logits, cache = decode_step(
            self.model, params, cache, last_tok[:, None], posid[:, None],
            None, cache_positions=wpos)
        tok = jnp.argmax(logits[:, -1, :].astype(jnp.float32),
                         axis=-1).astype(jnp.int32)
        return cache, tok

    def _make_catchup(self, bucket: int):
        """Jitted lane catch-up: write ``bucket`` history tokens' draft
        KV at absolute positions ``wpos..`` of one lane (gather the row,
        one multi-token cached forward, scatter back). Logits are
        discarded — catch-up is KV ingestion only."""
        from fleetx_tpu.models.gpt.generation import decode_step

        max_pos = self.model.cfg.max_position_embeddings

        def catchup(params, cache, ids, wpos, slot):
            params = self._dequant(params)
            small = _gather_slot(cache, slot)
            pos = jnp.minimum(
                wpos + jnp.arange(bucket, dtype=jnp.int32),
                max_pos - 1)[None, :]
            _, small = decode_step(self.model, params, small, ids[None, :],
                                   pos, None, cache_positions=wpos[None])
            return scatter_slot(cache, small, slot)

        return jax.jit(catchup)

    def _catchup(self, slot: int, hist: np.ndarray) -> None:
        """Prefill ``hist[lengths[slot] : len(hist)-1]`` into the lane
        (the last history token is next tick's feed, like the engine)."""
        lo = int(self.lengths[slot])
        hi = len(hist) - 1
        n = hi - lo
        if n <= 0:
            return
        bucket = -(-n // self.prefill_bucket) * self.prefill_bucket
        bucket = min(max(bucket, n), self.cache_len - lo)
        fn = self._catchup_jits.get(bucket)
        if fn is None:
            fn = self._catchup_jits[bucket] = self._make_catchup(bucket)
        padded = np.zeros(bucket, np.int32)
        padded[:n] = hist[lo:hi]
        self.cache = fn(self.params, self.cache, jnp.asarray(padded),
                        jnp.asarray(lo, jnp.int32),
                        jnp.asarray(slot, jnp.int32))
        self.lengths[slot] = hi

    def propose(self, requests: SpecRequests, k: int
                ) -> Dict[int, np.ndarray]:
        """Catch each lane up to its history, then ``k`` batched greedy
        draft steps; returns per-lane proposals clipped to their caps."""
        out: Dict[int, np.ndarray] = {}
        self._written = {}
        if not requests or k <= 0:
            return out
        for slot in sorted(requests):
            hist, _ = requests[slot]
            if self.lengths[slot] > len(hist) - 1:
                self.lengths[slot] = 0  # reused lane: rebuild from zero
            self._catchup(slot, np.asarray(hist, np.int64))
            self.last_tok[slot] = int(hist[-1])
        active = np.zeros(self.slots, bool)
        for slot, (_, cap) in requests.items():
            if cap > 0:
                active[slot] = True
        if not active.any():
            return out
        cur = jnp.asarray(self.last_tok)
        lens = jnp.asarray(self.lengths.astype(np.int32))
        act = jnp.asarray(active)
        cache = self.cache
        cols = []
        for i in range(k):
            cache, tok = self._step_jit(self.params, cache, cur,
                                        lens + i, act)
            cur = tok
            cols.append(np.asarray(tok))
        self.cache = cache
        for slot, (_, cap) in requests.items():
            if active[slot]:
                self._written[slot] = k
                out[slot] = np.asarray([c[slot] for c in cols[:cap]],
                                       np.int32)
        return out

    def observe(self, slot: int, emitted: int) -> None:
        """Advance the lane past the verified tokens: of the ``k`` draft
        positions propose() wrote (feeding last_tok, d1, ..), the first
        ``emitted`` hold correct-history KV (accepted drafts ARE the
        emitted tokens); the rest is the rejected tail the pointer
        rewind abandons. A fully-accepted tick leaves the lane one
        token short — the next propose()'s catch-up writes it."""
        self.lengths[slot] += min(emitted, self._written.pop(slot, 0))

    def on_retire(self, slot: int) -> None:
        """Free the lane; the next tenant's catch-up overwrites from 0
        (stale rows beyond the live window are never attended)."""
        self.lengths[slot] = 0
        self._written.pop(slot, None)

    def reset(self) -> None:
        """Engine recovery: drop every lane pointer; the next propose()
        re-prefills each lane from the (host-truth) history it is
        handed — deterministic, so post-recovery drafts are the same
        drafts."""
        self.lengths[:] = 0
        self._written = {}


def build_proposer(kind: str, model, variables,
                   prefill_bucket: int = 32) -> "Proposer":
    """Resolve ``FLEETX_SERVING_SPEC_DRAFT`` to a proposer: unset/``0``/
    ``ngram`` = prompt-lookup drafting; ``1``/``self`` = a draft-model
    proposer drafting with the serving model itself (every draft
    accepted — a correctness/testing configuration, not a speedup; real
    deployments pass a small model via the ``spec_proposer`` kwarg)."""
    kind = (kind or "").strip().lower()
    if kind in ("", "0", "ngram"):
        return NgramProposer()
    if kind in ("1", "self"):
        return DraftModelProposer(model, variables,
                                  prefill_bucket=prefill_bucket)
    raise ValueError(
        f"FLEETX_SERVING_SPEC_DRAFT={kind!r}: expected 'ngram' (default), "
        "or '1'/'self' (draft with the serving model itself); custom draft "
        "models ride the ServingEngine(spec_proposer=...) kwarg")
