"""Engine-shaped RPC proxy: drive a remote replica like a local engine.

``ReplicaClient(url)`` presents the exact ``ServingEngine`` surface the
:class:`~fleetx_tpu.serving.router.ServingRouter` consumes — the
attributes (``role``, ``paged``, ``page_size``, ``cache_len``,
``model.cfg.max_position_embeddings``) scraped from ``/rpc/spec`` at
connect, and the ten methods forwarded over
:func:`~fleetx_tpu.serving.api.wire.rpc_call` — so
``ServingRouter(replicas=[ReplicaClient(u) for u in urls])`` just works,
fallbacks included.

The load-bearing part is the NETWORK-FAILURE MAPPING. Every transport
failure surfaces as the exception (or sentinel) the router's existing
resilience ladder already handles for an in-process replica:

==================  ====================  ==============================
method              on ``ConnectionError``  router behavior it triggers
==================  ====================  ==============================
``health``          propagates            probe reads it as ``dead`` →
                                          SUSPECT/backoff escalation
``step``            ``ReplicaKilled``     ``_mark_dead`` → zero-token-
                                          loss ``history=`` migration
``submit``          ``QueueFull``         exclude + retry other
                                          replicas (request waits, never
                                          errors)
``take_result``     returns ``None``      keep polling / migrate
``emitted_tokens``  returns ``None``      re-base from router's record
``prefilled_ready`` returns ``[]``        no handoffs this tick
``cancel``          returns ``False``     a dead replica IS cancelled
``request_shutdown``  swallowed           already down = already drained
``declare_dead``    swallowed             already down = already dead
``export_kv``       propagates            handoff aborts → decode-side
                                          replay fallback
==================  ====================  ==============================

Typed replica-side errors (``error_kind`` bodies) re-raise as the real
exception classes via the wire module, so ``except QueueFull`` /
``except ValueError`` clauses in the router fire identically either way.

Streaming crosses the boundary inside ``/rpc/step`` responses: the
server buffers the tick's ``on_token`` events and the client replays
them — in emission order — into the callbacks registered at
:meth:`submit`. A lost step response therefore delivers NO events, the
router migrates from exactly the tokens it has seen, and the user
stream stays loss- and duplicate-free.
"""

from __future__ import annotations

import time
from types import SimpleNamespace
from typing import Dict, List, Optional

from fleetx_tpu.resilience.faults import ReplicaKilled
from fleetx_tpu.serving.api import wire

__all__ = ["ReplicaClient"]


class ReplicaClient:
    """An engine-shaped handle on one remote replica process."""

    def __init__(self, url: str, *, timeout_s: float = 10.0,
                 connect_wait_s: float = 0.0):
        self.url = url.rstrip("/")
        self.timeout_s = float(timeout_s)
        spec = self._fetch_spec(connect_wait_s)
        self.role = spec.get("role", "both")
        self.paged = bool(spec.get("paged"))
        self.page_size = spec.get("page_size") or 0
        self.cache_len = int(spec.get("cache_len", 0))
        self.slots = int(spec.get("slots", 1))
        self.eos_token_id = spec.get("eos_token_id")
        self.vocab_size = int(spec.get("vocab_size", 0))
        # the nested attribute path the router reads for the shared
        # request-length limit, mirrored from the spec scrape
        self.model = SimpleNamespace(cfg=SimpleNamespace(
            max_position_embeddings=int(spec.get(
                "max_position_embeddings", self.cache_len or 1)),
            vocab_size=self.vocab_size))
        # on_token callbacks by ENGINE rid, fed by step-event replay
        self._cbs: Dict[int, object] = {}

    def _fetch_spec(self, wait_s: float) -> Dict:
        """Scrape ``/rpc/spec``, retrying for up to ``wait_s`` seconds
        (the launcher connects while replica processes are still
        binding their ports)."""
        deadline = time.monotonic() + max(0.0, wait_s)
        while True:
            try:
                return wire.rpc_call(self.url + "/rpc/spec",
                                     timeout_s=self.timeout_s,
                                     method="spec")
            except ConnectionError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.05)

    def _rpc(self, name: str, payload: Dict) -> Dict:
        return wire.rpc_call(f"{self.url}/rpc/{name}", payload,
                             timeout_s=self.timeout_s, method=name)

    # --------------------------------------------- the engine surface

    def submit(self, prompt, *, on_token=None, rng_key=None, history=None,
               kv_payloads=None, **kw) -> int:
        """Forward ``submit`` with the wire codecs. An unreachable
        replica raises :class:`QueueFull` — the router then excludes it
        and retries the others with ``only_refusals=False``, so the
        request waits instead of erroring. Typed replica-side refusals
        (real ``QueueFull``/``ShuttingDown``/``ValueError``) cross
        as themselves."""
        payload = {
            "prompt": [int(t) for t in prompt],
            "rng_key": wire.rng_key_to_wire(rng_key),
            "history": (None if history is None
                        else [int(t) for t in history]),
            "kv_payloads": wire.b64_blobs_encode(kv_payloads),
            "kw": _json_kwargs(kw),
        }
        try:
            rid = int(self._rpc("submit", payload)["id"])
        except ConnectionError as e:
            from fleetx_tpu.serving.engine import QueueFull

            raise QueueFull(f"replica {self.url} unreachable at submit "
                            f"({e})") from e
        if on_token is not None:
            self._cbs[rid] = on_token
        return rid

    def step(self) -> Dict:
        """One remote tick. Replays the tick's ``on_token`` events into
        the registered callbacks (emission order), then returns the
        summary. An unreachable replica raises
        :class:`~fleetx_tpu.resilience.faults.ReplicaKilled` — the
        router's dead-replica migration path."""
        try:
            out = self._rpc("step", {})
        except ConnectionError as e:
            raise ReplicaKilled(
                f"replica {self.url} unreachable at step ({e})") from e
        self._replay(out.get("events", ()))
        return out.get("summary", {})

    def _replay(self, events) -> None:
        """Hand buffered ``on_token`` events to their callbacks, in
        emission order."""
        for erid, tok, finished in events:
            cb = self._cbs.get(erid)
            if cb is not None:
                cb(erid, tok, bool(finished))
                if finished:
                    self._cbs.pop(erid, None)

    def health(self) -> Dict:
        """The replica's ``/healthz`` body (its engine's ``health()``
        dict). An unreachable replica RAISES — the router probe's
        catch-all already reads a raising health as ``dead``."""
        return wire.rpc_call(self.url + "/healthz",
                             timeout_s=self.timeout_s, method="health")

    def take_result(self, request_id: int):
        """The finished :class:`ServingResult`, or ``None`` while in
        flight — and ``None`` when unreachable (the router keeps
        polling, then migrates when the probe declares death)."""
        try:
            out = self._rpc("take_result", {"id": int(request_id)})
        except ConnectionError:
            return None
        res = wire.result_from_wire(out.get("result"))
        if res is not None:
            self._cbs.pop(int(request_id), None)
        return res

    def emitted_tokens(self, request_id: int) -> Optional[List[int]]:
        """Tokens the replica has emitted for a live request (``None``
        when unknown or unreachable — the router keeps its own record
        as the migration source of truth)."""
        try:
            return self._rpc("emitted_tokens",
                             {"id": int(request_id)}).get("tokens")
        except ConnectionError:
            return None

    def prefilled_ready(self) -> List[int]:
        """Parked prefill-complete request ids (``[]`` when
        unreachable: no handoffs from a dead prefill replica — the
        decode side's replay fallback owns those requests now)."""
        try:
            return list(self._rpc("prefilled_ready", {}).get("ids", []))
        except ConnectionError:
            return []

    def export_kv(self, request_id: int) -> List[bytes]:
        """The crc32-trailed KV page wire blobs for a parked prefill.
        Raises ``KeyError`` (not parked) and ``ConnectionError``
        (unreachable) — both abort this handoff attempt and leave the
        router's decode-side replay fallback in charge."""
        out = self._rpc("export_kv", {"id": int(request_id)})
        return wire.b64_blobs_decode(out["payloads"]) or []

    def cancel(self, request_id: int) -> bool:
        """Cancel remotely; an unreachable replica returns ``False``
        (nothing left to cancel). The replica reads its tick in flight
        before the cancel acts, so the response carries the tokens that
        emitted (the cancelled request's last one among them); after
        those the local callback is dropped, so no late event replays
        for a request the router abandoned."""
        try:
            out = self._rpc("cancel", {"id": int(request_id)})
        except ConnectionError:
            out = {"cancelled": False}
        self._replay(out.get("events", ()))
        self._cbs.pop(int(request_id), None)
        return bool(out["cancelled"])

    def request_shutdown(self, grace_s: Optional[float] = None) -> None:
        """Flip the remote engine to draining (SIGTERM semantics). An
        unreachable replica is swallowed: already down = already
        drained."""
        try:
            self._rpc("request_shutdown", {"grace_s": grace_s})
        except ConnectionError:
            pass

    def declare_dead(self) -> None:
        """Tell the remote engine it has been failed out (mirror of
        ``ServingEngine.declare_dead``). Swallowed when unreachable."""
        try:
            self._rpc("declare_dead", {})
        except ConnectionError:
            pass


def _json_kwargs(kw: Dict) -> Dict:
    """Per-request override kwargs, coerced to JSON scalars (numpy ints
    from upstream samplers must not poison the wire)."""
    out = {}
    for k, v in kw.items():
        if v is None or isinstance(v, (bool, str)):
            out[k] = v
        elif isinstance(v, float):
            out[k] = float(v)
        else:
            try:
                out[k] = int(v)
            except (TypeError, ValueError):
                out[k] = v
    return out
