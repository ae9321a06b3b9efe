"""The paged KV-cache manager for continuous-batching decode.

:class:`PagedKVCacheManager` is the serving engine's one storage layout:
K/V live in ONE shared pool of lane-dense
``[num_pages, page_size, heads*head_dim]`` pages (the layout the
flash-decode kernels block, ops/pallas/decode_attention.py "Layout");
each request holds a block table mapping its logical positions to
physical pages (vLLM-style). Cache capacity and prefill compute track
tokens actually live, not per-lane worst case: a short request pins
pages for ITS tokens only, and requests sharing a token prefix share the
prefix's pages through a refcounted trie (:class:`PagePool`) — one
prefill serves them all.

It relies on the flash-decode live-window contract
(ops/pallas/decode_attention.py) to skip ALL buffer zeroing:

- each row's attention window is ``[0, lengths[row] + 1)`` — the per-row
  ``end`` the serving decode step derives from its write positions — so
  K/V a *previous* tenant left beyond the current length (or in a
  recycled page) is never attended;
- a fresh tenant's prefill overwrites its window's positions and every
  decode tick overwrites position ``lengths[row]`` *before* the window
  grows to include it, so stale rows are always replaced before they
  become visible.

The paged pool reserves physical page 0 as the TRASH page: zeroed block-
table entries (freed lanes, logical pages not yet allocated) route the
engine's pinned/tail writes there, so no write can land in a page owned
by someone else. Copy-on-write degenerates to an invariant instead of a
copy: only FULL prompt pages are ever shared (registered in the trie),
writes only target positions >= the shared prefix length, and those
positions live in freshly-allocated refcount-1 pages — a shared page is
structurally read-only.

The scalar ``cache_index`` leaves inside the cache tree are unused on
the serving path (per-row progress lives in ``lengths``; the model
receives explicit ``cache_positions`` instead) — see
``SelfAttention._update_cache``.

Quantized storage (``decode_kv_dtype="int8"`` on the model config, wired
by ``FLEETX_SERVING_KV_DTYPE``; docs/QUANTIZATION.md): the cache tree
built by ``init_decode_cache`` then carries int8 K/V leaves plus fp32
``cached_key_scale``/``cached_value_scale`` leaves of per-vector scales.
Nothing in this module special-cases them — the scale leaves share the
K/V leaves' trailing-rank layout (``[..., lanes|pages, len, heads]``
beside ``[..., lanes|pages, len, heads*head_dim]``: :data:`KV_LEAF_RANK`),
so every walker that addresses K/V by rank treats them alike and the page
lifecycle (trash-page routing, no-zeroing, refcounts) is dtype-blind:
a page's scales travel with its values because both are indexed by the
same block table. :meth:`_LaneBook.cache_nbytes` measures the actual
device bytes either way, which is how the ~2× HBM win is asserted.

Two classes of page (a model with window-attention layers,
``models/gpt/hybrid.py``): a full-attention layer keeps every token of a
lane, a window layer only the rows a live query can still see. The manager
then holds a second allocator, :class:`WindowPagePool`, beside
:class:`PagePool`: one table a lane and class, both in logical page order,
handed to the model together (``tables`` is ``[2, lanes, pages of a row]``:
0 full, 1 window). The full class is :class:`PagePool` as it always was
(without the prefix trie: a prefix's window pages are gone once the window
has passed them, so prefix reuse is refused at construction); the window
class allocates a lane's pages as its prefill chunks and decode ticks reach
them and releases each page once every row of it lies behind the window of
the lane's next query. Admission counts both classes.

EVA's two classes (a model with EVA attention, ``models/gpt/eva.py``): every
layer keeps BOTH. The class :class:`PagePool` holds is the SUMMARY class:
one pooled row for every chunk of ``eva_chunk_size`` positions, its table
addressed by chunk index, so the manager asks the pool for ``position //
chunk`` wherever it asks another model's for a position (``_rows``;
admission allocates the prompt's whole chunks, a tick's ``ensure_page`` the
row of the chunk it stands in, whose pooled row is held from then on but
attended only by queries of LATER windows). The WINDOW class is
:class:`WindowPagePool` with ``tumbling``: its table is addressed by
position, a lane holds the exact rows of its current window alone and gives
every page back at once when it crosses a multiple of ``eva_window_size``.
``tables`` is ``[2, lanes, pages of a row]``, summary (zeros behind its
fewer entries) then window; the model composes the two (``eva.py``).
Admission, ``free``, preemption and recovery move both, and a page is one
chunk.

Kinds of state, and their two homes (a model with recurrent layers,
``models/gpt/mixed_stack.py``). IN THE POOL (gated short-convolution
layers): beside the keys and values of its attention
layers a lane keeps, in every convolution layer, the operator's last
inputs. They live in TAIL PAGES of the same pool under the same block
table: physical page ``p`` has a few rows in every convolution layer, and
the state of position ``t`` is kept in the page that holds ``t``. Nothing
in :class:`PagePool` knows it: a full prompt page that enters the trie
carries, in its tail, the state as it stands at the page's end, and that
snapshot is shared, parked, revived and evicted WITH the page, because it
is the page. A prompt that matches a prefix therefore resumes the state at
whatever page boundary the match ends; a freed lane's zeroed table routes
its state writes to the trash page's tail, which nobody reads. The host
tiers and the page ship between replicas do not carry tails and are
refused for such a model at the engine's construction;
:meth:`PagedKVCacheManager.class_counters` counts the snapshots and the
bytes of either kind. ONCE A LANE (selective-scan layers, state kind
"ssm"; delta-rule linear attention layers, state kind "kda"): a state of
hundreds of kilobytes or of megabytes a layer cannot be kept a page, so
the cache tree holds it in leaves indexed by the LANE (``ssm_state``,
``ssm_conv``; ``kda_state``, ``kda_conv``: ``[layers, lanes, ...]``), beside
the pool and outside it. Which kind a model holds so, and in which leaves,
the MODEL says (``cfg.lane_state``: ``models/gpt/block_fields.py``
``LANE_STATE_LEAVES``); nothing here knows a kind by name.
The manager's part is the address: :attr:`PagedKVCacheManager.tables` then
has the lane's own index as column 0 of every row, before the pages, and
the model reads and writes the lane's state there. Its lifecycle is the
lane's and needs no program: a call at position 0 begins from zero (every
:meth:`PagedKVCacheManager.alloc` is counted under the kind's own name,
``ssm_state_resets`` or ``kda_state_resets``), a
later chunk and a tick carry on from what the lane holds, a freed lane's
state is dead weight until the next request begins over it. Nothing of it
is shared: prefix reuse is refused for such a model at construction (a
match would need the state as it stood at the match's end), as are the
host tiers and the page ship.

Two-level page cache (``FLEETX_SERVING_HOST_CACHE_BYTES``;
docs/SERVING.md): with a :class:`HostPageStore` attached, LRU eviction
of a zero-ref warm trie subtree SPILLS each page's content (K/V and, at
int8, the scale pages — every cache leaf) to bounded host DRAM instead
of destroying it. Entries are keyed by the page's full token-chunk path
from the trie root, so they are content-addressed: a later prompt
carrying the same prefix revives them into fresh physical pages via one
batched device transfer per cache leaf, an engine ``recover()`` that
rebuilds the pool from scratch still matches them (the engine re-threads
the same store), and a stale entry can never be wrong — deterministic
prefill means identical tokens produce identical K/V. The pool stays
pure-host: the actual device reads/writes go through ``spill_fn`` /
``revive_fn`` callbacks the :class:`PagedKVCacheManager` binds (tests
drive the pool with dummy payloads, no backend needed).
"""

from __future__ import annotations

import hashlib
import heapq
import math
import os
import struct
import zlib
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

__all__ = ["DiskPageStore", "HostPageStore", "KV_LEAF_RANK", "PagePool",
           "PagedKVCacheManager", "TieredPageStore", "WindowPagePool",
           "leaf_device_nbytes", "window_lane_pages"]

# Trailing rank of every K/V (and int8 scale) cache leaf:
# [batch | pages, positions, lanes] as SelfAttention._update_cache stores
# them. The nn.scan layer stack, when present, sits in front, so the
# batch/page axis is ``ndim - KV_LEAF_RANK``; leaves below this rank are
# the ``cache_index`` scalars.
KV_LEAF_RANK = 3


def leaf_device_nbytes(leaf) -> int:
    """PER-DEVICE bytes of one array: the addressable shard's size, not
    the global one. Under a mesh-sharded serving engine the KV cache
    leaves split their heads axis over ``mp``, so the bytes a device
    actually holds — the number HBM capacity planning cares about — is
    the shard, and on a single device the shard IS the array."""
    shape = tuple(getattr(leaf, "shape", ()))
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None and hasattr(sharding, "shard_shape"):
        try:
            shape = sharding.shard_shape(shape)
        except Exception:  # abstract/tracer leaves: fall back to global
            pass
    return int(math.prod(shape)) * np.dtype(leaf.dtype).itemsize


class HostPageStore:
    """Bounded host-DRAM spill tier for KV pages (module docstring).

    A byte-budgeted LRU dict: ``key`` is a page's full token-chunk path
    (tuple of full-page token tuples from the trie root) and the payload
    is whatever the spilling manager handed over (per-leaf host arrays).
    Keys are content-addressed, so the store outlives any one
    :class:`PagePool`/:class:`PagedKVCacheManager` — the engine owns the
    store and re-threads it through ``recover()``'s rebuilt manager.
    Capacity pressure drops the OLDEST entries (counted in
    ``evicted_pages``); a payload larger than the whole budget is
    rejected outright. Pure host state, no locking (the serving engine
    is single-threaded per replica)."""

    def __init__(self, capacity_bytes: int):
        if capacity_bytes < 0:
            raise ValueError(
                f"capacity_bytes must be >= 0, got {capacity_bytes}")
        self.capacity_bytes = int(capacity_bytes)
        self._entries: Dict[tuple, Tuple[object, int]] = {}  # insertion=LRU
        self.nbytes = 0
        self.spilled_pages = 0  # lifetime puts accepted
        self.revived_pages = 0  # lifetime pops on a prefix match
        self.evicted_pages = 0  # lifetime drops (capacity pressure)

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    def put(self, key, payload, nbytes: int) -> bool:
        """Insert one spilled page, evicting oldest entries until it
        fits; False (nothing stored) when ``nbytes`` exceeds the whole
        budget. Re-putting a key refreshes its payload and LRU slot."""
        if nbytes > self.capacity_bytes:
            return False
        old = self._entries.pop(key, None)
        if old is not None:
            self.nbytes -= old[1]
        while self.nbytes + nbytes > self.capacity_bytes and self._entries:
            k = next(iter(self._entries))
            self.nbytes -= self._entries.pop(k)[1]
            self.evicted_pages += 1
        self._entries[key] = (payload, nbytes)
        self.nbytes += nbytes
        self.spilled_pages += 1
        return True

    def get(self, key):
        """A matched page's payload for revival, refreshing its LRU
        slot. The entry STAYS — the tier is inclusive: the device gets a
        copy, and a fault that destroys the device copy (rollback,
        recovery, re-eviction) can revive this entry again. A later
        re-spill of the same path overwrites it with identical bytes
        (content-addressed keys cannot go stale). KeyError if absent."""
        payload, nbytes = self._entries.pop(key)
        self._entries[key] = (payload, nbytes)  # re-insert = LRU refresh
        self.revived_pages += 1
        return payload

    def pop(self, key):
        """Remove and return an entry's payload (explicit invalidation;
        the revive path uses :meth:`get`). KeyError if absent."""
        payload, nbytes = self._entries.pop(key)
        self.nbytes -= nbytes
        return payload

    def check_invariants(self) -> None:
        """Byte accounting must match the entries exactly and respect
        the budget (called from :meth:`PagePool.check_invariants`)."""
        want = sum(nb for _, nb in self._entries.values())
        assert self.nbytes == want, (
            f"host store nbytes {self.nbytes} != sum of entries {want}")
        assert self.nbytes <= self.capacity_bytes, (
            f"host store over budget: {self.nbytes} > {self.capacity_bytes}")

    # ------------------------------------------------- payload wire format
    # One spilled page's payload is a per-cache-leaf list of host arrays
    # (K page, V page, int8 scale pages when quantized) with None holding
    # the slots of lower-rank leaves (the cache_index scalars that never
    # spill). to_bytes/from_bytes give that payload a PICKLE-FREE,
    # byte-exact wire form — the page-ship primitive the disaggregated
    # prefill/decode split and the shared DiskPageStore serialize over
    # (docs/SERVING.md "Disaggregated prefill/decode"), with none of
    # pickle's arbitrary-code-execution surface on the receiving replica.
    # Layout (little-endian): magic "FXPG" + u16 version + u16 entry
    # count, then per entry a none/array flag and, for arrays, dtype
    # string + shape + raw C-order bytes; a crc32 of everything before it
    # trails the whole blob (v2 — a page shipped across processes or read
    # back off disk must fail loudly on any bit flip, never revive
    # garbage K/V into a live cache).

    _MAGIC = b"FXPG"
    _VERSION = 2  # v2 = v1 + crc32 trailer; v1 blobs are rejected

    @staticmethod
    def payload_to_bytes(payload) -> bytes:
        """Serialize one spill payload (list of ``Optional[np.ndarray]``)
        to the wire format above. Byte-exact: dtypes (int8 values, fp32
        scales, bf16 via its numpy extension name) and shapes round-trip
        losslessly through :meth:`payload_from_bytes`."""
        out = [HostPageStore._MAGIC,
               struct.pack("<HH", HostPageStore._VERSION, len(payload))]
        for arr in payload:
            if arr is None:
                out.append(b"\x00")
                continue
            a = np.ascontiguousarray(arr)
            if a.dtype.names is not None or a.dtype.hasobject:
                raise ValueError(
                    f"payload leaf dtype {a.dtype} is not a plain array "
                    "dtype; only numeric cache leaves spill")
            # dtype.name, not dtype.str: the extension dtypes (bfloat16)
            # stringify as opaque void types under .str but round-trip
            # through np.dtype(name) once ml_dtypes is registered (jax
            # imports it)
            name = a.dtype.name.encode("ascii")
            out.append(b"\x01")
            out.append(struct.pack("<B", len(name)))
            out.append(name)
            out.append(struct.pack("<B", a.ndim))
            out.append(struct.pack(f"<{a.ndim}I", *a.shape))
            raw = a.tobytes()
            out.append(struct.pack("<Q", len(raw)))
            out.append(raw)
        body = b"".join(out)
        return body + struct.pack("<I", zlib.crc32(body))

    @staticmethod
    def payload_from_bytes(buf: bytes) -> list:
        """Inverse of :meth:`payload_to_bytes` (malformed/truncated/
        corrupted input raises ValueError — a corrupt shipped page must
        fail loudly, not revive garbage K/V). The crc32 trailer is
        verified BEFORE any entry is parsed, and pre-crc v1 blobs are
        rejected by version with an explicit error."""
        view = memoryview(buf)
        if bytes(view[:4]) != HostPageStore._MAGIC:
            raise ValueError("not a HostPageStore payload (bad magic)")
        if len(buf) < 12:  # magic + header + crc32 trailer
            raise ValueError(
                f"truncated payload: {len(buf)} bytes is shorter than the "
                "8-byte header + 4-byte crc32 trailer")
        version, count = struct.unpack("<HH", view[4:8])
        if version != HostPageStore._VERSION:
            raise ValueError(
                f"unsupported payload version {version}: this build "
                f"writes/reads v{HostPageStore._VERSION} (crc32-trailed); "
                "v1 predates the checksum — re-spill the page with a "
                "current build")
        (want_crc,) = struct.unpack("<I", view[-4:])
        got_crc = zlib.crc32(view[:-4])
        if got_crc != want_crc:
            raise ValueError(
                f"payload crc32 mismatch (stored {want_crc:#010x}, "
                f"computed {got_crc:#010x}): the page was corrupted in "
                "flight or at rest")
        end = len(buf) - 4
        pos, out = 8, []
        try:
            for _ in range(count):
                flag = view[pos]
                pos += 1
                if flag == 0:
                    out.append(None)
                    continue
                nlen = view[pos]
                pos += 1
                dtype = np.dtype(bytes(view[pos:pos + nlen]).decode("ascii"))
                pos += nlen
                ndim = view[pos]
                pos += 1
                shape = struct.unpack(f"<{ndim}I",
                                      view[pos:pos + 4 * ndim])
                pos += 4 * ndim
                (nbytes,) = struct.unpack("<Q", view[pos:pos + 8])
                pos += 8
                arr = np.frombuffer(
                    view[pos:pos + nbytes], dtype=dtype).reshape(shape)
                pos += nbytes
                out.append(arr.copy())  # own the memory, not the buffer
        except (struct.error, ValueError, IndexError, TypeError) as e:
            # IndexError: memoryview read past a truncation point;
            # TypeError: np.dtype() on a truncated dtype name — both are
            # the same "corrupt payload" condition the contract promises
            # to surface as ValueError (the crc check above catches
            # virtually all of these first; this is defense in depth
            # against a collision)
            raise ValueError(f"truncated/corrupt payload: {e}") from None
        if pos != end:
            raise ValueError(
                f"payload has {end - pos} trailing bytes before the crc")
        return out


class DiskPageStore:
    """Content-addressed, byte-bounded KV page store on shared disk —
    the cluster tier of the page cache (``FLEETX_SERVING_DISK_CACHE_DIR``
    / ``_BYTES``; docs/SERVING.md "Disaggregated prefill/decode").

    Same ``put``/``get``/``pop``/``in`` surface as :class:`HostPageStore`
    so :class:`PagePool` drives either (or both, via
    :class:`TieredPageStore`) without caring, but entries live as files
    under one directory EVERY replica in the fleet points at: a hot
    system prompt prefilled by any one replica is revivable by all of
    them, sustaining prefix hit rate past any single replica's host-DRAM
    budget. Filenames are the sha256 of the page's full token-chunk path
    (content-addressed — identical tokens produce identical K/V, so a
    file written by replica A is correct for replica B by construction),
    contents are the crc32-trailed :meth:`HostPageStore.payload_to_bytes`
    wire format (a corrupted file fails loudly at decode, never revives
    garbage). Writes are atomic (tmp + rename) so a reader never sees a
    half-written page; eviction is LRU by mtime over a directory scan,
    which stays coherent when several replica processes share the dir
    (``get`` touches the file). Capacity accounting is by actual file
    bytes — the serialized page, not the host-array footprint."""

    _SUFFIX = ".fxpg"

    def __init__(self, cache_dir: str, capacity_bytes: int):
        if capacity_bytes < 0:
            raise ValueError(
                f"capacity_bytes must be >= 0, got {capacity_bytes}")
        if not cache_dir:
            raise ValueError("cache_dir must be a non-empty path")
        self.cache_dir = str(cache_dir)
        self.capacity_bytes = int(capacity_bytes)
        os.makedirs(self.cache_dir, exist_ok=True)
        self.spilled_pages = 0  # lifetime puts accepted (this instance)
        self.revived_pages = 0  # lifetime gets served
        self.evicted_pages = 0  # lifetime files dropped under the budget
        self.hits = 0           # gets served (alias kept for the gauge)
        self.misses = 0         # membership probes that found nothing

    # ----------------------------------------------------------- addressing
    def _path(self, key) -> str:
        """File path for a token-chunk-path key: sha256 over the chunks
        (chunk boundaries separated so ``((1,2),)`` and ``((1,),(2,))``
        cannot collide), hex digest as the filename."""
        h = hashlib.sha256()
        for chunk in key:
            h.update(np.asarray(chunk, np.int64).tobytes())
            h.update(b"/")
        return os.path.join(self.cache_dir, h.hexdigest() + self._SUFFIX)

    def _files(self):
        """(path, stat) for every store file, oldest-mtime first.
        Concurrently vanished files (a sibling replica evicted them) are
        skipped — the scan must tolerate sharing."""
        out = []
        try:
            names = os.listdir(self.cache_dir)
        except OSError:
            return []
        for name in names:
            if not name.endswith(self._SUFFIX):
                continue
            path = os.path.join(self.cache_dir, name)
            try:
                out.append((path, os.stat(path)))
            except OSError:
                continue
        out.sort(key=lambda ps: (ps[1].st_mtime, ps[0]))
        return out

    def __len__(self) -> int:
        return len(self._files())

    @property
    def nbytes(self) -> int:
        """Bytes currently resident (actual file sizes — shared-dir
        coherent: siblings' writes count too)."""
        return sum(st.st_size for _, st in self._files())

    def __contains__(self, key) -> bool:
        if os.path.exists(self._path(key)):
            return True
        self.misses += 1
        return False

    def put(self, key, payload, nbytes: int = 0) -> bool:
        """Serialize + store one page under its content address,
        evicting oldest files until the budget holds; False (nothing
        stored) when the serialized page alone exceeds it. ``nbytes``
        (the host-array footprint the pool computed) is advisory here —
        disk accounting uses the wire bytes actually written."""
        del nbytes  # accounted from the serialized blob below
        blob = HostPageStore.payload_to_bytes(payload)
        if len(blob) > self.capacity_bytes:
            return False
        path = self._path(key)
        tmp = path + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)  # atomic: readers see old bytes or new
        except OSError:
            # full or read-only shared dir: the disk tier degrades to
            # nothing-stored, it must never fault the serving tick
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        self.spilled_pages += 1
        total = self.nbytes
        if total > self.capacity_bytes:
            for victim, st in self._files():
                if victim == path:
                    continue  # never evict the page just written
                try:
                    os.remove(victim)
                except OSError:
                    continue
                self.evicted_pages += 1
                total -= st.st_size
                if total <= self.capacity_bytes:
                    break
        return True

    def get(self, key):
        """Decode a stored page back to its host-array payload,
        refreshing its LRU slot (mtime touch — visible to every replica
        sharing the dir). KeyError when absent; ValueError when the file
        is corrupt (crc/format — the caller must treat that as a miss
        that fails loudly, not revive it)."""
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            raise KeyError(key) from None
        try:
            payload = HostPageStore.payload_from_bytes(blob)
        except ValueError:
            # self-heal: a corrupt entry must not outlive its first read,
            # or every prompt matching this prefix would re-hit it
            try:
                os.remove(path)
            except OSError:
                pass
            self.misses += 1  # a corrupt file reads as a (loud) miss
            raise
        try:
            os.utime(path)
        except OSError:
            pass  # a sibling evicted it mid-read; the payload is ours
        self.revived_pages += 1
        self.hits += 1
        return payload

    def pop(self, key):
        """Remove and return an entry's payload (explicit invalidation).
        KeyError if absent — or corrupt: ``get`` unlinks the bad file,
        so either way no entry remains afterwards."""
        try:
            payload = self.get(key)
        except ValueError:
            raise KeyError(key) from None
        self.hits -= 1  # a pop is not a cache hit
        self.revived_pages -= 1
        try:
            os.remove(self._path(key))
        except OSError:
            pass
        return payload

    def check_invariants(self) -> None:
        """Resident bytes must respect the budget. Tolerates transient
        overshoot only from files a SIBLING process wrote after this
        instance's last eviction pass — within one process the budget is
        re-enforced on every put."""
        total = self.nbytes
        assert total <= self.capacity_bytes or len(self._files()) <= 1, (
            f"disk store over budget: {total} > {self.capacity_bytes}")


class TieredPageStore:
    """Host-DRAM tier over a shared disk tier, behind the one store
    surface :class:`PagePool` drives (docs/SERVING.md "Disaggregated
    prefill/decode"): puts write through to both (the local replica keeps
    DRAM-speed revives, the fleet gets the page), gets serve host-first
    and fall back to disk — promoting a disk hit back into the host tier
    so a hot cross-replica prefix pays the file read once. The
    host-facing counters/properties delegate to the host tier (so
    ``ServingMetrics.observe_host_tier`` reads a tiered store unchanged);
    disk counters are scraped off ``.disk`` via ``observe_disk_tier``."""

    def __init__(self, host: HostPageStore, disk: DiskPageStore):
        self.host = host
        self.disk = disk

    def __len__(self) -> int:
        return len(self.host)

    @property
    def nbytes(self) -> int:
        return self.host.nbytes

    @property
    def capacity_bytes(self) -> int:
        return self.host.capacity_bytes

    @property
    def spilled_pages(self) -> int:
        return self.host.spilled_pages

    @property
    def revived_pages(self) -> int:
        return self.host.revived_pages

    @property
    def evicted_pages(self) -> int:
        return self.host.evicted_pages

    def __contains__(self, key) -> bool:
        return key in self.host or key in self.disk

    def put(self, key, payload, nbytes: int) -> bool:
        """Write-through: True when either tier kept the page."""
        kept_host = self.host.put(key, payload, nbytes)
        kept_disk = self.disk.put(key, payload, nbytes)
        return kept_host or kept_disk

    def get(self, key):
        """Host tier first; a disk hit is promoted into the host tier
        (counted as a host spill, like any other insertion)."""
        try:
            return self.host.get(key)
        except KeyError:
            pass
        payload = self.disk.get(key)
        nbytes = sum(a.nbytes for a in payload if a is not None)
        self.host.put(key, payload, nbytes)
        return payload

    def pop(self, key):
        """Invalidate in both tiers; payload from whichever had it."""
        payload = None
        try:
            payload = self.host.pop(key)
        except KeyError:
            pass
        try:
            disk_payload = self.disk.pop(key)
            payload = payload if payload is not None else disk_payload
        except KeyError:
            pass
        if payload is None:
            raise KeyError(key)
        return payload

    def check_invariants(self) -> None:
        self.host.check_invariants()
        self.disk.check_invariants()


class _LaneBook:
    """Decode-lane bookkeeping of the cache manager: a min-heap
    free list (lowest lane first, deterministic, O(log n) alloc/free —
    the original list re-sorted on every release), per-lane request ids,
    and the HOST mirror of per-lane live lengths (the device copy rides
    the engine's state dict) — kept for observability without a device
    sync."""

    def _init_lanes(self, slots: int) -> None:
        if slots < 1:
            raise ValueError(f"need at least one slot, got {slots}")
        self.slots = slots
        self.lengths = np.zeros(slots, np.int64)
        self.request_ids: List[Optional[int]] = [None] * slots
        self._free: List[int] = list(range(slots))

    @property
    def free_count(self) -> int:
        """Number of decode lanes available for admission."""
        return len(self._free)

    @property
    def active_count(self) -> int:
        """Number of decode lanes currently holding a live request."""
        return self.slots - len(self._free)

    def occupancy(self) -> float:
        """Fraction of decode lanes holding a live request."""
        return self.active_count / self.slots

    def _claim_lane(self, request_id: int, length: int) -> int:
        lane = heapq.heappop(self._free)
        self.request_ids[lane] = request_id
        self.lengths[lane] = length
        return lane

    def _release_lane(self, slot: int) -> None:
        if self.request_ids[slot] is None:
            raise ValueError(f"slot {slot} is already free")
        self.request_ids[slot] = None
        self.lengths[slot] = 0
        heapq.heappush(self._free, slot)

    def cache_nbytes(self) -> int:
        """PER-DEVICE bytes of the live cache tree, measured from the
        actual leaves (int8 values + fp32 scales when kv-quantized,
        full-width K/V otherwise; the addressable shard when the engine
        sharded the heads over a mesh) — the scrapeable ground truth for
        the quantized ~½× AND the mesh ÷mp HBM stories
        (``fleetx_serving_kv_cache_bytes``)."""
        return sum(leaf_device_nbytes(leaf)
                   for leaf in jax.tree.leaves(self.cache))


class _TrieNode:
    """One full page of prompt tokens in the prefix trie: ``key`` is the
    page's token tuple, ``page`` its physical index; children extend the
    prefix by one more full page. The node path from the root IS the
    prefix hash — dict lookups chunk by chunk, no rolling hash to
    collide."""

    __slots__ = ("key", "page", "parent", "children")

    def __init__(self, key, page: int, parent: "_TrieNode" = None):
        self.key = key
        self.page = page
        self.parent = parent
        self.children: Dict[tuple, "_TrieNode"] = {}


class PagePool:
    """Host-side page allocator + refcounted prefix trie (PURE host state
    — no device arrays, so allocator/trie invariants are unit-testable
    without a model or backend).

    Physical page 0 is the reserved TRASH page (module docstring): it is
    born with a permanent refcount, never enters the free stack, and
    absorbs every write routed through a zeroed block-table entry.

    Lifecycle of a shareable page: a full prompt page is prefilled into a
    refcount-1 page, registered in the trie (``register_prefix``), and
    from then on other lanes' ``alloc`` calls can match it (refcount++).
    When its last holder frees, the page parks in ``_cached`` — content
    intact, trie node alive — where a later match revives it for free or
    LRU eviction reclaims it (evicting a node evicts its whole subtree:
    children's refcounts can never exceed their parent's, so a refcount-0
    parent guarantees refcount-0 children and nothing live is stranded).

    Alloc/free cost: O(pages touched) with an O(1) free-stack — no sort,
    no scan of the pool."""

    def __init__(self, num_pages: int, page_size: int, lanes: int,
                 lane_pages: int, prefix_cache: bool = True,
                 host_store: Optional[HostPageStore] = None,
                 spill_fn: Optional[Callable] = None,
                 revive_fn: Optional[Callable] = None):
        if page_size < 1:
            raise ValueError(f"page_size must be positive, got {page_size}")
        if num_pages < lane_pages + 1:
            raise ValueError(
                f"num_pages {num_pages} cannot hold one full lane "
                f"({lane_pages} pages) plus the trash page")
        self.num_pages = num_pages
        self.page_size = page_size
        self.lanes = lanes
        self.lane_pages = lane_pages
        self.prefix_cache = prefix_cache
        # block tables: 0 = trash page = "not allocated"
        self.tables = np.zeros((lanes, lane_pages), np.int32)
        self.alloc_counts = np.zeros(lanes, np.int64)
        self.shared_counts = np.zeros(lanes, np.int64)
        self.ref = np.zeros(num_pages, np.int64)
        self.ref[0] = 1  # trash page: permanently pinned
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._root = _TrieNode(None, 0, None)
        self._node_of_page: Dict[int, _TrieNode] = {}
        # refcount-0 pages still registered in the trie, insertion order =
        # LRU (dicts preserve it; moves re-insert)
        self._cached: Dict[int, _TrieNode] = {}
        # bumped on every block-table change so the engine re-uploads the
        # device copy only when something moved
        self.version = 0
        # host spill tier (module docstring): active only when all three
        # pieces are present AND the trie is on (spilled entries are
        # matched by token-chunk path — without the trie nothing could
        # ever revive them)
        self.host_store = (host_store if prefix_cache and spill_fn
                           and revive_fn else None)
        self._spill_fn = spill_fn
        self._revive_fn = revive_fn
        # the trie's traffic, counted in pages: entered, matched by an
        # alloc, and evicted (with recurrent state in the pages' tails these
        # are the snapshots written, resumed from and lost)
        self.registered = self.matched = self.evicted = 0
        self.matched_allocs = 0

    # ------------------------------------------------------------- stats

    @property
    def usable_pages(self) -> int:
        """Pages available to requests (the pool minus the trash page)."""
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        """Pages obtainable right now: the free stack plus refcount-0
        cached pages (reclaimable by LRU eviction)."""
        return len(self._free) + len(self._cached)

    @property
    def pages_in_use(self) -> int:
        """Pages pinned by at least one live lane."""
        return self.usable_pages - self.free_pages

    @property
    def cached_pages(self) -> int:
        """Refcount-0 pages kept warm in the trie (reclaimable)."""
        return len(self._cached)

    def occupancy(self) -> float:
        """Fraction of usable pages pinned by live lanes."""
        return self.pages_in_use / max(self.usable_pages, 1)

    # ------------------------------------------------------------ helpers

    def _chunks(self, tokens) -> List[tuple]:
        """Full-page token tuples of a prompt, capped so at least the last
        prompt token is always re-prefilled (its logits seed the first
        sampled token — a 100% trie hit would leave nothing to run)."""
        n = (len(tokens) - 1) // self.page_size
        return [tuple(int(t) for t in
                      tokens[i * self.page_size:(i + 1) * self.page_size])
                for i in range(n)]

    def _match_path(self, chunks) -> List[_TrieNode]:
        path, node = [], self._root
        for c in chunks:
            node = node.children.get(c)
            if node is None:
                break
            path.append(node)
        return path

    def _take_page(self) -> Optional[int]:
        """Pop a free page; when the stack is dry, evict the LRU cached
        prefix subtree (all refcount-0 by the parent>=child invariant) —
        spilling its pages to the host tier first when one is attached."""
        if not self._free:
            if not self._cached:
                return None
            node = next(iter(self._cached.values()))  # oldest zero-ref
            self._evict_subtree(node)
        return self._free.pop()

    @staticmethod
    def _node_key(node: _TrieNode) -> tuple:
        """A node's full token-chunk path from the root — the content
        address its spilled payload is stored under."""
        parts = []
        while node is not None and node.key is not None:
            parts.append(node.key)
            node = node.parent
        return tuple(reversed(parts))

    def _evict_subtree(self, node: _TrieNode) -> None:
        """Reclaim a zero-ref cached subtree's physical pages. With a
        host tier attached, each page's content is spilled (ONE batched
        device read for the whole subtree) before the page frees; the
        warm data then survives as host entries revivable by token path.
        Without one, this is plain destruction (the pre-spill behavior).
        """
        if node.parent is not None:
            del node.parent.children[node.key]
        victims: List[_TrieNode] = []
        stack = [node]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            victims.append(n)
        if self.host_store is not None and victims:
            keys = [self._node_key(n) for n in victims]
            for (payload, nbytes), key in zip(
                    self._spill_fn([n.page for n in victims]), keys):
                self.host_store.put(key, payload, nbytes)
        self.evicted += len(victims)
        for n in victims:
            self._cached.pop(n.page, None)
            del self._node_of_page[n.page]
            self._free.append(n.page)
            n.children = {}
            n.parent = None

    def _match_host(self, chunks: List[tuple],
                    path: List[_TrieNode]) -> List[tuple]:
        """Continue a trie prefix match into the host spill tier: the
        chunk paths extending ``path`` that have spilled payloads. Stops
        at the first miss (a revived page is only attendable if every
        page before it is present too)."""
        if self.host_store is None:
            return []
        key = self._node_key(path[-1]) if path else ()
        out = []
        for c in chunks[len(path):]:
            key = key + (c,)
            if key not in self.host_store:
                break
            out.append(key)
        return out

    # ----------------------------------------------------------- requests

    def pages_needed(self, tokens) -> int:
        """Pages an ``alloc`` of this prompt would draw from the
        free/reclaimable pool: fresh pages covering the non-shared part
        of ``[0, prompt_len]`` (the +1 slot is the first sampled token's
        write position), PLUS matched prefix pages currently parked in
        the warm cache — revival moves those out of the reclaimable
        count, so they cost pool capacity exactly like a fresh claim."""
        chunks = self._chunks(tokens) if self.prefix_cache else []
        path = self._match_path(chunks)
        fresh = len(tokens) // self.page_size + 1 - len(path)
        revived = sum(1 for n in path if self.ref[n.page] == 0)
        return fresh + revived

    def can_admit(self, tokens) -> bool:
        """Page-granular admission check: True iff ``alloc`` would
        succeed right now (exact — kept in lockstep with ``alloc``'s own
        availability accounting, so the engine can pop-then-alloc)."""
        return self.pages_needed(tokens) <= self.free_pages

    def alloc(self, lane: int, tokens) -> Optional[int]:
        """Build ``lane``'s block table for prompt ``tokens``: shared
        prefix pages from the trie (refcount++), host-spilled prefix
        pages revived into fresh physical pages (one batched device
        write), plus fresh refcount-1 pages covering the rest of
        ``[0, prompt_len]``. Returns the shared prefix length in TOKENS —
        trie-shared AND host-revived pages both skip their prefill — or
        None, with no state committed, when the pool cannot supply the
        physical pages (host revivals draw from the same free pool as
        fresh claims, so :meth:`pages_needed` already counts them)."""
        if self.alloc_counts[lane]:
            raise ValueError(f"lane {lane} already holds pages")
        need_total = len(tokens) // self.page_size + 1
        if need_total > self.lane_pages:
            # checked BEFORE any ref is committed: an over-long prompt
            # must raise cleanly, not corrupt the pool mid-claim
            raise ValueError(
                f"prompt of {len(tokens)} tokens needs {need_total} logical "
                f"pages; a lane holds {self.lane_pages}")
        chunks = self._chunks(tokens) if self.prefix_cache else []
        path = self._match_path(chunks)
        # commit the matched refs FIRST: revived pages leave _cached, so
        # the availability check below sees the true reclaimable count and
        # eviction can no longer touch the matched path (ref > 0)
        for n in path:
            if self.ref[n.page] == 0:
                del self._cached[n.page]
            self.ref[n.page] += 1
        fresh = need_total - len(path)  # incl. any host-revived pages
        if fresh > self.free_pages:
            for n in reversed(path):  # unwind: nothing committed
                self.ref[n.page] -= 1
                if self.ref[n.page] == 0:
                    self._cached[n.page] = n
            return None
        # grab matched host payloads BEFORE drawing pages: a draw can
        # trigger more spills, and the store's capacity pressure could
        # evict an entry this alloc is about to revive (the local
        # reference keeps the payload alive either way — the tier is
        # inclusive, see HostPageStore.get)
        host_keys = self._match_host(chunks, path)
        payloads = []
        for k in host_keys:
            try:
                payloads.append(self.host_store.get(k))
            except (KeyError, ValueError):
                # _match_host's membership check raced a sibling
                # replica's eviction (KeyError) or the file failed its
                # crc (ValueError — the disk store unlinks it): this key
                # and every key after it (unattendable without it) read
                # as misses and fall through to fresh prefill. The trie
                # refs committed above stay valid either way, and the
                # pool draw is unchanged (a revived page and a fresh
                # page cost the same), so nothing needs unwinding.
                break
        host_keys = host_keys[:len(payloads)]
        row = self.tables[lane]
        row[:] = 0
        for i, n in enumerate(path):
            row[i] = n.page
        parent = path[-1] if path else self._root
        revive = []
        for j, key in enumerate(host_keys):
            # revived pages re-enter the trie as regular registered pages
            # (refcount 1, shareable immediately) at fresh physical homes
            page = self._take_page()
            self.ref[page] = 1
            row[len(path) + j] = page
            node = _TrieNode(key[-1], page, parent)
            parent.children[key[-1]] = node
            self._node_of_page[page] = node
            parent = node
            revive.append((page, payloads[j]))
        for i in range(len(path) + len(host_keys), need_total):
            page = self._take_page()
            self.ref[page] = 1
            row[i] = page
        if revive:
            self._revive_fn(revive)
        self.alloc_counts[lane] = need_total
        self.shared_counts[lane] = len(path) + len(host_keys)
        self.matched += len(path)
        self.matched_allocs += bool(path)
        self.version += 1
        return (len(path) + len(host_keys)) * self.page_size

    def register_prefix(self, lane: int, tokens) -> None:
        """Insert ``lane``'s freshly-prefilled FULL prompt pages into the
        trie so later prompts can share them. Idempotent over the already-
        matched prefix; only refcount-1 pages this lane exclusively owns
        are ever registered (the copy-on-write invariant: pages become
        shareable exactly when they will never be written again)."""
        if not self.prefix_cache:
            return
        node = self._root
        row = self.tables[lane]
        for i, c in enumerate(self._chunks(tokens)):
            nxt = node.children.get(c)
            if nxt is None:
                nxt = _TrieNode(c, int(row[i]), node)
                node.children[c] = nxt
                self._node_of_page[nxt.page] = nxt
                self.registered += 1
            node = nxt

    def ensure_page(self, lane: int, pos: int) -> bool:
        """Make logical position ``pos`` writable for ``lane`` (grow-on-
        demand: the engine calls this before each decode tick's write).
        False = the pool is dry (caller retires the request), or ``pos``
        is past the lane's logical capacity."""
        li = pos // self.page_size
        if li < self.alloc_counts[lane]:
            return True
        if li >= self.lane_pages:
            return False
        page = self._take_page()
        if page is None:
            return False
        self.ref[page] = 1
        self.tables[lane, li] = page
        self.alloc_counts[lane] = li + 1
        self.version += 1
        return True

    def ensure_span(self, lane: int, pos: int, n: int) -> int:
        """Make as many of logical positions ``[pos, pos + n)`` writable
        for ``lane`` as the pool can supply, allocating pages in order
        (the speculative-decoding verify write: one slot for the pending
        token plus up to k draft tokens). Returns the count of LEADING
        covered positions — the engine clamps the lane's draft length to
        ``covered - 1`` so no accepted token's K/V can ever land on the
        trash page, while the un-covered tail's writes route there
        harmlessly (rejected-draft territory by construction)."""
        covered = 0
        for i in range(n):
            if not self.ensure_page(lane, pos + i):
                break
            covered += 1
        return covered

    def trim_lane(self, lane: int, live_tokens: int) -> int:
        """Release ``lane``'s pages beyond those covering its
        ``live_tokens`` valid positions — the speculative tick's
        post-verify cleanup, returning rejected-draft pages to the pool
        the same tick so a lane's transient draft window can never
        starve a NEIGHBOR'S next pending-token allocation (the plain
        engine would not have held those pages, and byte parity demands
        identical ``cache_full`` decisions). Only unshared
        (refcount-1, trie-unregistered) tail pages are eligible — draft
        pages always are, prompt/prefix pages always sit inside the
        live span. Returns the number of pages released."""
        need = (max(int(live_tokens), 1) - 1) // self.page_size + 1
        freed = 0
        for i in range(int(self.alloc_counts[lane]) - 1, need - 1, -1):
            page = int(self.tables[lane, i])
            if self.ref[page] != 1 or page in self._node_of_page:
                break  # shared/registered page past the live span:
            self.ref[page] = 0  # structurally impossible — stop cold
            self._free.append(page)
            self.tables[lane, i] = 0
            self.alloc_counts[lane] = i
            freed += 1
        if freed:
            self.version += 1
        return freed

    def check_invariants(self) -> None:
        """Assert the pool's conservation/refcount invariants; raises
        AssertionError with a specific message on any breach. The chaos
        suite calls this after EVERY injected failure — a rolled-back or
        recovered tick must leave the allocator exactly as consistent as a
        clean one (docs/RESILIENCE.md)."""
        # conservation: every usable page is free, cached, or lane-held
        held = set()
        for lane in range(self.lanes):
            n = int(self.alloc_counts[lane])
            for i in range(n):
                p = int(self.tables[lane, i])
                assert p != 0, f"lane {lane} logical page {i} maps to trash"
                held.add(p)
            for i in range(n, self.lane_pages):
                assert self.tables[lane, i] == 0, (
                    f"lane {lane} logical page {i} beyond alloc_count {n} "
                    f"is {self.tables[lane, i]}, not trash")
        free = set(self._free)
        cached = set(self._cached)
        assert not (free & cached), f"pages both free and cached: {free & cached}"
        assert not (free & held), f"pages both free and lane-held: {free & held}"
        assert not (cached & held), (
            f"pages both cached and lane-held: {cached & held}")
        assert free | cached | held == set(range(1, self.num_pages)), (
            "page conservation broken: "
            f"{len(free)} free + {len(cached)} cached + {len(held)} held "
            f"!= {self.num_pages - 1} usable")
        # refcounts: trash pinned, cached zero-ref, held = #lanes holding
        assert self.ref[0] >= 1, "trash page unpinned"
        counts = {p: 0 for p in range(1, self.num_pages)}
        for lane in range(self.lanes):
            for i in range(int(self.alloc_counts[lane])):
                counts[int(self.tables[lane, i])] += 1
        for p in range(1, self.num_pages):
            want = counts[p]
            assert self.ref[p] == want, (
                f"page {p} refcount {self.ref[p]} != {want} lane holders")
            if p in cached or p in free:
                assert want == 0
        # trie: every cached page has a live node; parent >= child refs
        for p, node in self._cached.items():
            assert self._node_of_page.get(p) is node, (
                f"cached page {p} lost its trie node")
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            for c in n.children.values():
                assert self.ref[c.page] <= self.ref[n.page], (
                    f"trie child page {c.page} (ref {self.ref[c.page]}) "
                    f"outlives parent {n.page} (ref {self.ref[n.page]})")
                stack.append(c)
        # host tier: byte accounting exact, and no key shadows a LIVE trie
        # path (a spilled entry for a path that is back in the trie is
        # merely stale-but-valid — content-addressed keys cannot be wrong
        # — but the trie must win the match, so it never revives)
        if self.host_store is not None:
            self.host_store.check_invariants()

    def free(self, lane: int) -> None:
        """Release every page of ``lane``'s chain (refcount--). Zero-ref
        pages return to the free stack — unless they are trie-registered,
        in which case they park in the LRU cache with content intact so
        the next matching prompt revives them for free."""
        if not self.alloc_counts[lane]:
            raise ValueError(f"lane {lane} holds no pages (double-freed?)")
        row = self.tables[lane]
        for i in range(int(self.alloc_counts[lane])):
            page = int(row[i])
            if self.ref[page] <= 0:
                raise ValueError(
                    f"page {page} of lane {lane} double-freed")
            self.ref[page] -= 1
            if self.ref[page] == 0:
                node = self._node_of_page.get(page)
                if node is not None:
                    self._cached[page] = node
                else:
                    self._free.append(page)
        row[:] = 0
        self.alloc_counts[lane] = 0
        self.shared_counts[lane] = 0
        self.version += 1


def window_lane_pages(window: int, span: int, page_size: int) -> int:
    """The most pages of the window class one lane can hold: ``window +
    span`` tokens (``span``: the most one program writes, a prefill chunk),
    a page more for where they begin and stop inside a page."""
    return -(-(window + span) // page_size) + 1


class WindowPagePool:
    """Host-side allocator of the WINDOW class of pages (module docstring
    "Two classes of page"): pure host state, like :class:`PagePool`, with
    the part of its surface the manager needs.

    A lane's table is in logical page order, as the full class's, but only
    the pages ``[first, end)`` are held: ``prepare(lane, pos, n)``, called
    before a program writes positions ``[pos, pos + n)`` (a prefill chunk,
    or one decode token), first RELEASES every page whose rows all lie
    before ``pos - window + 1`` (the earliest key the query at ``pos`` sees;
    later queries of the lane see later keys only), then allocates through
    ``pos + n - 1``. A released entry reads 0, the trash page, which the
    model's ``starts`` keeps outside every read. So a lane never holds more
    than ``window + n`` tokens rounded out to pages (:attr:`lane_pages`
    for ``n`` up to ``span``), and a pool of ``lanes * lane_pages + 1``
    pages cannot run dry. Page 0 is the trash page.

    ``tumbling`` (EVA attention, module docstring "EVA's two classes"): the
    window does not slide. The query at ``pos`` sees the rows ``[window *
    (pos // window), pos]``, so ``prepare`` releases ALL the lane's pages at
    once when ``pos`` crosses a multiple of ``window`` (counted in
    :attr:`tumbled`) and a lane never holds more than ``window // page_size``
    pages."""

    def __init__(self, num_pages: int, page_size: int, lanes: int,
                 table_pages: int, window: int, span: int,
                 tumbling: bool = False):
        if window < 1 or span < 1:
            raise ValueError(f"window {window} and span {span} must be "
                             "positive")
        if tumbling and (window % page_size or window % span):
            raise ValueError(
                f"a tumbling window of {window} rows holds whole pages "
                f"({page_size}) and whole spans ({span}): a program never "
                "straddles a boundary")
        self.num_pages = num_pages
        self.page_size = page_size
        self.lanes = lanes
        self.window = window
        self.span = span
        self.tumbling = tumbling
        self.tumbled = 0      # windows released whole, ever (tumbling)
        # (a tumbling window's rows ``[window * (pos // window), pos]`` are
        # never more than the window's own pages)
        self.lane_pages = min(table_pages, window // page_size if tumbling
                              else window_lane_pages(window, span, page_size))
        if num_pages < self.lane_pages + 1:
            raise ValueError(
                f"window pool of {num_pages} pages cannot hold one lane's "
                f"window ({self.lane_pages} pages) plus the trash page")
        self.tables = np.zeros((lanes, table_pages), np.int32)
        self.first = np.zeros(lanes, np.int64)   # held pages: [first, end)
        self.end = np.zeros(lanes, np.int64)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self.version = 0
        self.recycled = 0     # pages released behind a window, ever

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.usable_pages - len(self._free)

    def pages_needed(self, n_tokens: int) -> int:
        """Pages a request of ``n_tokens`` prompt tokens comes to hold in
        this class (its first decode write included)."""
        return min(n_tokens // self.page_size + 1, self.lane_pages)

    def can_admit(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= len(self._free)

    def prepare(self, lane: int, pos: int, n: int = 1) -> bool:
        """Make positions ``[pos, pos + n)`` writable for ``lane`` and let
        go of what no query from ``pos`` on can see. False: the pool is dry
        (what was released stays released; the caller retires the request)
        or the span runs past the table."""
        ps = self.page_size
        row = self.tables[lane]
        moved = False
        # the first page a query from ``pos`` on can see
        lo = min(max(pos - self.window + 1, 0) // ps, row.shape[0])
        if self.tumbling:  # its window's first: the one before goes whole
            lo = min(pos // self.window * self.window // ps, row.shape[0])
            self.tumbled += int(self.first[lane] < lo <= self.end[lane])
        for i in range(int(self.first[lane]), min(lo, int(self.end[lane]))):
            self._free.append(int(row[i]))
            row[i] = 0
            self.recycled += 1
            moved = True
        if lo > self.first[lane]:
            self.first[lane] = lo
            self.end[lane] = max(int(self.end[lane]), lo)
        last = (pos + n - 1) // ps
        ok = last < row.shape[0]
        for i in range(int(self.end[lane]), min(last, row.shape[0] - 1) + 1):
            if not self._free:
                ok = False
                break
            row[i] = self._free.pop()
            self.end[lane] = i + 1
            moved = True
        if moved:
            self.version += 1
        return ok

    def free(self, lane: int) -> None:
        """Release every page ``lane`` holds."""
        row = self.tables[lane]
        for i in range(int(self.first[lane]), int(self.end[lane])):
            self._free.append(int(row[i]))
        row[:] = 0
        self.first[lane] = self.end[lane] = 0
        self.version += 1

    def check_invariants(self) -> None:
        """Conservation: every usable page is free or held by exactly one
        lane inside its ``[first, end)``, and nothing else is in a table."""
        held = []
        for lane in range(self.lanes):
            row = self.tables[lane]
            lo, hi = int(self.first[lane]), int(self.end[lane])
            assert 0 <= lo <= hi <= len(row), (lane, lo, hi)
            assert hi - lo <= self.lane_pages, (
                f"lane {lane} holds {hi - lo} window pages, more than "
                f"{self.lane_pages}")
            assert not row[:lo].any() and not row[hi:].any(), (
                f"lane {lane} has window pages outside [{lo}, {hi})")
            assert row[lo:hi].all(), f"lane {lane} holds the trash page"
            held += row[lo:hi].tolist()
        assert len(held) == len(set(held)), "a window page is held twice"
        assert not set(held) & set(self._free), "a held window page is free"
        assert len(held) + len(self._free) == self.usable_pages, (
            f"window pages lost: {len(held)} held + {len(self._free)} free "
            f"!= {self.usable_pages}")


class PagedKVCacheManager(_LaneBook):
    """Page-granular decode cache + lane bookkeeping (module docstring
    has the design).

    Decode *lanes* (batch rows of the jitted step) are allocated
    lowest-free-first, but storage admission is by PAGES: a lane is
    only claimable when :class:`PagePool` can cover the prompt, and the
    chain grows page-by-page as the request decodes. ``cache`` is the live
    device tree of ``[num_pages, page_size, heads*head_dim]`` leaves;
    ``tables`` the host block tables the engine uploads when ``version``
    moves."""

    def __init__(self, model, slots: int, cache_len: int, num_pages: int,
                 page_size: int, prefix_cache: bool = True,
                 host_store: Optional[HostPageStore] = None,
                 window_span: int = 0):
        from fleetx_tpu.models.gpt.generation import init_decode_cache

        if page_size % 8:
            raise ValueError(
                f"page_size must be a multiple of 8 (flash-decode tiling "
                f"contract), got {page_size}")
        if cache_len % page_size:
            raise ValueError(
                f"cache_len {cache_len} must be a multiple of page_size "
                f"{page_size}")
        cfg = model.cfg
        if (cfg.decode_cache_len, cfg.decode_num_pages,
                cfg.decode_page_size) != (cache_len, num_pages, page_size):
            raise ValueError(
                "model cfg (decode_cache_len, decode_num_pages, "
                f"decode_page_size) = ({cfg.decode_cache_len}, "
                f"{cfg.decode_num_pages}, {cfg.decode_page_size}) must "
                f"match the manager's ({cache_len}, {num_pages}, "
                f"{page_size})")
        # the kinds of state a lane keeps (module docstring, "Kinds of
        # state"): keys and values, a convolution's tail rows in the pool,
        # a selective scan's or a delta rule's state once a lane (the model
        # names that kind and the leaves it holds)
        self.state_kinds = tuple(getattr(cfg, "state_kinds", ("kv",)))
        self.lane_state_kind, lane_leaves = getattr(cfg, "lane_state",
                                                    ("", ()))
        self.lane_state = bool(self.lane_state_kind)
        if self.lane_state and prefix_cache:
            raise ValueError(
                "prefix reuse over selective-scan or delta-rule layers "
                f"(state kind {self.lane_state_kind!r}): a lane's state is "
                "kept once a lane, not at page boundaries, so a matched "
                "prefix has no state to resume from (prefix_cache=False)")
        self._init_lanes(slots)
        self.cache_len = cache_len
        self.page_size = page_size
        self.num_pages = num_pages
        self.host_store = host_store
        self._revive_jit = self._make_revive_jit()
        # EVA's summary class (module docstring "EVA's two classes"): the
        # class ``num_pages`` counts holds one row a CHUNK of positions, and
        # the pool is asked for a position's chunk
        self.eva_chunk = int(getattr(cfg, "eva_chunk_size", 0) or 0)
        eva_window = int(getattr(cfg, "eva_window_size", 0) or 0)
        if self.eva_chunk and (
                prefix_cache or page_size != self.eva_chunk
                or eva_window // self.eva_chunk % page_size
                or not getattr(cfg, "decode_window_pages", None)):
            raise ValueError(
                "EVA attention's two classes of page need prefix_cache=False "
                "(a matched prefix would need its summary pages alone: not "
                f"built), page_size {page_size} == eva_chunk_size "
                f"{self.eva_chunk} (a page of the window class is one "
                "chunk), whole summary pages to a window, and "
                "decode_window_pages (the serving engine sets it)")
        self.pool = PagePool(num_pages, page_size, slots,
                             -(-self._rows(cache_len) // page_size),
                             prefix_cache,
                             host_store=host_store,
                             spill_fn=self._spill_pages,
                             revive_fn=self._revive_pages)
        # the second class of page (module docstring): a model with window
        # layers says how many pages one of them owns
        self.window_pool = None
        self.admits_refused = {"full": 0, "window": 0}
        window_pages = getattr(cfg, "decode_window_pages", None)
        if self.eva_chunk:
            if window_span < 1 or window_span % self.eva_chunk:
                raise ValueError(
                    "EVA attention needs chunked prefill in whole chunks: "
                    f"window_span {window_span} (the engine's prefill_chunk) "
                    f"over eva_chunk_size {self.eva_chunk}")
            self.window_pool = WindowPagePool(
                window_pages, page_size, slots, cache_len // page_size,
                eva_window, window_span, tumbling=True)
        elif window_pages:
            if prefix_cache:
                raise ValueError(
                    "prefix reuse over window-attention layers: a prefix's "
                    "window pages are released once the window has passed "
                    "them, so there is nothing to share (prefix_cache=False)")
            if window_span < 1:
                raise ValueError(
                    "window-attention layers need chunked prefill: "
                    "window_span (the engine's prefill_chunk) bounds what a "
                    "lane's window pages hold beside the window")
            self.window_pool = WindowPagePool(
                window_pages, page_size, slots, cache_len // page_size,
                cfg.sliding_window, window_span)
        self.cache = init_decode_cache(model, slots)
        # bytes one page holds of each kind of state in the pool (keys and
        # values in the attention layers, the tail rows of a model's
        # convolution layers), and bytes one LANE holds outside it
        self.page_bytes = {"kv": 0, "conv": 0}
        self.lane_bytes = 0
        self.state_resets = 0
        self.index_pool_bytes = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.cache)[0]:
            name = getattr(path[-1], "key", "")
            kind = {"cached_key": "kv", "cached_value": "kv",
                    "cached_index": "kv", "conv_state": "conv"}.get(name)
            if name == "cached_index":  # (a latent pool's third leaf)
                self.index_pool_bytes = leaf_device_nbytes(leaf)
            if kind and self.state_kinds != ("kv",):
                self.page_bytes[kind] += leaf_device_nbytes(leaf) // num_pages
            elif name in lane_leaves:
                self.lane_bytes += leaf_device_nbytes(leaf) // slots

    # ------------------------------------------------------ host spill tier

    def _spill_pages(self, pages: List[int]) -> List[Tuple[list, int]]:
        """Read ``pages`` out of the device pool as host payloads — one
        batched gather + transfer per cache leaf for the whole list (the
        subtree being evicted), not one per page. A payload is the
        per-leaf list of that page's slices (K, V, and the int8 scale
        pages when quantized); leaves below :data:`KV_LEAF_RANK`
        (``cache_index`` scalars) ride as None."""
        import jax.numpy as jnp

        from fleetx_tpu.obs.events import emit as obs_emit

        idx = jnp.asarray(pages, jnp.int32)
        per_leaf = []
        for leaf in jax.tree.leaves(self.cache):
            if leaf.ndim < KV_LEAF_RANK:
                per_leaf.append(None)
                continue
            # the page axis (scan-stacked or unrolled)
            ax = leaf.ndim - KV_LEAF_RANK
            taken = jnp.moveaxis(jnp.take(leaf, idx, axis=ax), ax, 0)
            per_leaf.append(np.asarray(jax.device_get(taken)))
        out = []
        for j in range(len(pages)):
            payload = [None if a is None else a[j] for a in per_leaf]
            nbytes = sum(a.nbytes for a in payload if a is not None)
            out.append((payload, nbytes))
        obs_emit("page_spill", pages=len(pages))
        return out

    def _make_revive_jit(self):
        """Jitted batched revival: one scatter per cache leaf, with the
        old pool buffers DONATED on TPU so XLA updates the pages in
        place — an eager ``.at[].set`` would copy every full-size pool
        leaf per revival, transiently doubling the cache's HBM footprint
        the engine's donation discipline exists to avoid. jax.jit's own
        shape-keyed cache gives one compile per distinct batch size
        (bounded by lane_pages, like the engine's prefill buckets)."""

        def revive(leaves, pages, updates):
            out = []
            for leaf, upd in zip(leaves, updates):
                ax = leaf.ndim - KV_LEAF_RANK
                index = (slice(None),) * ax + (pages,)
                out.append(leaf.at[index].set(upd))
            return out

        donate = jax.default_backend() == "tpu"
        return jax.jit(revive, donate_argnums=(0,) if donate else ())

    def _revive_pages(self, entries: List[Tuple[int, list]]) -> None:
        """Write spilled payloads back into fresh physical ``pages`` —
        one batched host→device transfer + in-place scatter per cache
        leaf for every page an alloc revives (the "batched device_put"
        the revive path promises)."""
        import jax.numpy as jnp

        from fleetx_tpu.obs.events import emit as obs_emit

        pages = jnp.asarray([p for p, _ in entries], jnp.int32)
        leaves, treedef = jax.tree.flatten(self.cache)
        big = [i for i, leaf in enumerate(leaves)
               if leaf.ndim >= KV_LEAF_RANK]
        updates = [
            np.moveaxis(np.stack([payload[i] for _, payload in entries]),
                        0, leaves[i].ndim - KV_LEAF_RANK)
            for i in big
        ]
        new = self._revive_jit([leaves[i] for i in big], pages, updates)
        for i, leaf in zip(big, new):
            leaves[i] = leaf
        self.cache = jax.tree.unflatten(treedef, leaves)
        obs_emit("page_revive", pages=len(entries))

    # --------------------------------------------- cross-replica page ship
    # (docs/SERVING.md "Disaggregated prefill/decode"): a prefill-role
    # replica reads a finished prompt's pages out through the SAME
    # batched per-leaf device reads the spill tier uses, and a decode-
    # role replica writes shipped payloads into its own fresh pages
    # through the SAME batched revive scatter — the ship path adds no new
    # device code, only the public names.

    def read_pages(self, pages: List[int]) -> List[list]:
        """Read physical ``pages`` out of the device pool as host
        payloads (one per page, each a per-cache-leaf list with None for
        lower-rank leaves — exactly what :meth:`HostPageStore
        .payload_to_bytes` serializes). One batched gather + transfer
        per cache leaf for the whole list, int8 scale pages included."""
        return [payload for payload, _ in self._spill_pages(pages)]

    def revive_pages(self, entries: List[Tuple[int, list]]) -> None:
        """Write ``(physical_page, payload)`` entries into the device
        pool — the decode-role half of a KV handoff, one batched
        host→device transfer + in-place scatter per cache leaf. The
        caller owns the bookkeeping: the pages must already be allocated
        to the receiving lane (``alloc``) and their payloads decoded and
        validated (``payload_from_bytes`` raises on corruption)."""
        self._revive_pages(entries)

    # ------------------------------------------------------- page surface

    @property
    def tables(self) -> np.ndarray:
        """Host block tables [slots, cache_len // page_size] int32; with a
        window class ``[2, slots, ...]``, full then window; for a model
        whose state is held once a lane ``[slots, 1 + pages]``, the lane's
        own index before its pages (module docstring, "Kinds of state")."""
        if self.lane_state:  # column 0: where the lane's own state is held
            return np.concatenate(
                [np.arange(self.slots, dtype=np.int32)[:, None],
                 self.pool.tables], axis=1)
        if self.window_pool is None:
            return self.pool.tables
        return np.stack([self._as_wide(self.pool.tables),
                         self.window_pool.tables])

    def _rows(self, tokens: int) -> int:
        """Rows of the class ``self.pool`` holds for ``tokens`` positions:
        as many, or one a whole chunk under EVA."""
        return tokens // self.eva_chunk if self.eva_chunk else tokens

    def _pool_tokens(self, tokens):
        """What ``self.pool`` is asked to hold for a prompt of ``tokens``:
        the prompt, or as many entries as it has whole chunks under EVA (the
        class has no trie: only the count is read)."""
        return tokens[:self._rows(len(tokens))] if self.eva_chunk else tokens

    def _as_wide(self, tables: np.ndarray) -> np.ndarray:
        """``self.pool``'s tables (or one lane's row) as wide as the window
        class's: EVA's summary class is addressed by chunk and has fewer
        entries a lane, zeros (the trash page) behind them."""
        more = self.window_pool.tables.shape[-1] - tables.shape[-1]
        if not more:
            return tables
        return np.pad(tables, [(0, 0)] * (tables.ndim - 1) + [(0, more)])

    def lane_tables(self, slot: int) -> np.ndarray:
        """``slot``'s row of :attr:`tables` (of every class: ``[2, ...]``
        with a window class)."""
        if self.lane_state:
            return np.concatenate([[np.int32(slot)], self.pool.tables[slot]])
        if self.window_pool is None:
            return self.pool.tables[slot]
        return np.stack([self._as_wide(self.pool.tables[slot]),
                         self.window_pool.tables[slot]])

    @property
    def tables_version(self) -> int:
        """Monotone counter: re-upload the device tables when it moves."""
        if self.window_pool is None:
            return self.pool.version
        return self.pool.version + self.window_pool.version

    def class_counters(self) -> dict:
        """The pool by class of page, for ``ServingMetrics.snapshot()``
        (through ``model_protocol.device_counters_of``); empty with one
        class."""
        if self.lane_state:
            return {
                # every lane's state is resident, whoever holds the lane
                "state_bytes_lanes": self.slots * self.lane_bytes,
                f"{self.lane_state_kind}_state_resets": self.state_resets,
                "kv_page_bytes_in_use": (self.pool.pages_in_use
                                         * self.page_bytes["kv"]),
                # the OTHER home of such a lane's state, where its pool's
                # rows are latents (a lane with two homes)
                **({"latent_pages_in_use": self.pool.pages_in_use,
                    "latent_page_bytes": self.page_bytes["kv"]}
                   if "latent" in self.state_kinds else {})}
        if "latent" in self.state_kinds:
            # one class of page, whose rows are latents: what is pinned by
            # live requests, and what the trie holds for a later match
            pool = self.pool
            return {"latent_pages_in_use": pool.pages_in_use,
                    "latent_pages_in_trie": len(pool._node_of_page),
                    "latent_page_bytes": self.page_bytes["kv"],
                    # of them, what the indexer's keys take (0 without one)
                    "index_pool_bytes": self.index_pool_bytes,
                    "state_bytes_lanes": 0,
                    "kv_page_bytes_in_use": (pool.pages_in_use
                                             * self.page_bytes["kv"])}
        if "conv" in self.state_kinds:
            pool, tail = self.pool, self.page_bytes["conv"]
            return {
                "state_snapshots_written": pool.registered,
                "state_snapshots_resumed": pool.matched_allocs,
                "state_snapshot_pages_matched": pool.matched,
                "state_snapshots_evicted": pool.evicted,
                # a lane's running state is the tail of its last page; a
                # snapshot the tail of a page the trie holds
                "state_bytes_lanes": self.active_count * tail,
                "state_bytes_snapshots": len(pool._node_of_page) * tail,
                "kv_page_bytes_in_use": (pool.pages_in_use
                                         * self.page_bytes["kv"])}
        if self.window_pool is None:
            return {}
        if self.eva_chunk:
            window = self.window_pool
            return {"pages_in_use_summary": self.pool.pages_in_use,
                    "pages_in_use_window": window.pages_in_use,
                    "usable_pages_summary": self.pool.usable_pages,
                    "usable_pages_window": window.usable_pages,
                    "window_pages_recycled": window.recycled,
                    "eva_windows_tumbled": window.tumbled,
                    "admits_refused_summary": self.admits_refused["full"],
                    "admits_refused_window": self.admits_refused["window"]}
        return {"pages_in_use_full": self.pool.pages_in_use,
                "pages_in_use_window": self.window_pool.pages_in_use,
                "usable_pages_full": self.pool.usable_pages,
                "usable_pages_window": self.window_pool.usable_pages,
                "window_pages_recycled": self.window_pool.recycled,
                "admits_refused_full": self.admits_refused["full"],
                "admits_refused_window": self.admits_refused["window"]}

    @property
    def pages_in_use(self) -> int:
        """Pages pinned by live requests (trash page excluded)."""
        return self.pool.pages_in_use

    @property
    def usable_pages(self) -> int:
        """Pages the pool can hand to requests."""
        return self.pool.usable_pages

    def page_occupancy(self) -> float:
        """Fraction of usable pages pinned by live requests."""
        return self.pool.occupancy()

    # ---------------------------------------------------------- lifecycle

    def refusal(self, tokens) -> Optional[str]:
        """What this prompt is short of right now: ``"lane"`` (none is
        free), ``"pages"`` (of either class), or None: :meth:`alloc` would
        succeed."""
        if not self._free:
            return "lane"
        if not self.pool.can_admit(self._pool_tokens(tokens)):
            self.admits_refused["full"] += 1
            return "pages"
        if (self.window_pool is not None
                and not self.window_pool.can_admit(len(tokens))):
            self.admits_refused["window"] += 1
            return "pages"
        return None

    def can_admit(self, tokens) -> bool:
        """A free lane AND enough free pages for this prompt right now,
        of every class."""
        return self.refusal(tokens) is None

    def alloc(self, request_id: int, tokens) -> Optional[Tuple[int, int]]:
        """Claim the lowest free lane + a page chain for prompt ``tokens``.
        Returns ``(lane, shared_len)`` — ``shared_len`` tokens of trie-
        shared prefix whose prefill is skipped — or None (nothing claimed)
        when lanes or pages are short."""
        if not self._free:
            return None
        if len(tokens) >= self.cache_len:
            # >= not >: a full-capacity prompt would need lane_pages + 1
            # logical pages (the first sampled token's slot) — and has no
            # decode room anyway, mirroring the engine's submit() guard
            raise ValueError(
                f"prompt_len {len(tokens)} leaves no decode room "
                f"(cache_len {self.cache_len})")
        lane = self._free[0]  # peek: only claim once pages are certain
        shared = self.pool.alloc(lane, self._pool_tokens(tokens))
        if shared is None:
            return None
        claimed = self._claim_lane(request_id, len(tokens))
        assert claimed == lane  # heap head == the lane the pool filled
        self.state_resets += self.lane_state  # its first call begins from 0
        return lane, shared

    def register_prefix(self, slot: int, tokens) -> None:
        """Publish ``slot``'s freshly-prefilled full prompt pages for
        sharing (see :meth:`PagePool.register_prefix`)."""
        self.pool.register_prefix(slot, tokens)

    def ensure_page(self, slot: int) -> bool:
        """Grow ``slot``'s chain to cover its next write position
        (``lengths[slot]``), in every class; False = a pool is dry, caller
        retires the request."""
        pos = int(self.lengths[slot])
        return self.pool.ensure_page(slot, self._rows(pos)) and (
            self.window_pool is None or self.window_pool.prepare(slot, pos))

    def prepare_span(self, slot: int, pos: int, n: int) -> bool:
        """Before a prefill call writes positions ``[pos, pos + n)`` of
        ``slot``: the window class lets go of what lies behind and
        allocates the span (the full class holds its pages since
        ``alloc``). True with one class."""
        return (self.window_pool is None
                or self.window_pool.prepare(slot, pos, n))

    def ensure_span(self, slot: int, n: int) -> int:
        """Grow ``slot``'s chain toward covering its next ``n`` write
        positions (the speculative verify window: pending token + k
        drafts); returns how many leading positions are covered — see
        :meth:`PagePool.ensure_span` for the draft-clamp contract."""
        return self.pool.ensure_span(slot, int(self.lengths[slot]), n)

    def trim_span(self, slot: int) -> int:
        """Release ``slot``'s pages past its live prefix (rejected-draft
        territory) back to the pool — see :meth:`PagePool.trim_lane`."""
        return self.pool.trim_lane(slot, int(self.lengths[slot]))

    def free(self, slot: int) -> None:
        """Release the lane and its page chain. No buffer zeroing — the
        live-window contract (module docstring) plus zeroed table entries
        (all writes re-route to the trash page) keep stale K/V dark."""
        if self.request_ids[slot] is None:
            raise ValueError(f"slot {slot} is already free")
        self.pool.free(slot)
        if self.window_pool is not None:
            self.window_pool.free(slot)
        self._release_lane(slot)
