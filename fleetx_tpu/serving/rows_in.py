"""Rows that are not tokens, positions that are not rows: what
``ServingEngine`` does for a model whose capabilities say ``takes_rows``
and ``mrope`` (docs/SERVING.md "Rows from a tower", "Positions apart from
rows"). A module of its own so that an engine over any other model imports
and traces nothing of it.

**At submit** (:func:`layout`, host only). ``submit(prompt, images=[...])``:
every run of ``image_token_id`` in ``prompt`` says where an image's rows go,
``h x w`` of them for an image of ``2h x 2w`` patches (a run may hold
several images one after the other; a mismatch of run and grids raises).
The host derives, a prompt row each:

- ``keys`` (int64), what the prefix trie is keyed by: a text row's id; an
  image row's 62-bit hash of the image's bytes (shape included) and the
  row's place in it, NEGATIVE, so that no image row is ever a token's;
- ``positions`` ``[3, rows]`` (time, height, width): a text row ``(n, n,
  n)``, ``n`` one past the largest position so far; an image of ``h x w``
  rows beginning at ``n``: ``(n, n + r, n + c)`` for row ``r``, column
  ``c``, and the next ``n`` is ``n + max(h, w)``;
- ``rope_delta``: the next position less the prompt's rows, which every
  decoded row's position stands past its cache row (installed with the
  lane: a tick's position is ``lengths + rope_delta`` on all three axes).

**On which thread.** What the ids and the images' SHAPES say
(:func:`outline`: the runs against the grids, ``positions``, ``rope_delta``,
each image's first row and grid, a text row's key, and every refusal) is
derived inside ``submit``, on the caller's thread, which reads no byte of an
image. The bytes are read on the tower's one WORKER thread
(:class:`_Worker`, alive only while it has work; an engine without a tower
has none): first every image's digest, after which the request's ``keys``
are whole and its ``keyed`` future done, then each image's patches, a
future of its own in the image's record. The caller leaves the arrays
unwritten until the request's first token or its end (docs/SERVING.md).
:func:`layout` is both stages at once, on the thread that calls it.

**At admission** the trie is matched by ``keys`` BEFORE any tower call: an
image wholly inside the match is neither encoded nor prefilled. A request
whose ``keyed`` is not done is not matched: as the head of the queue it
blocks admission that step, as a head too big for the pool does, and the
step goes on to its tick (``layout_blocked_steps``).

**In the step's prefill slot** (:class:`Tower`). Before a prefill program
takes rows ``[start, start + n)`` of a request, every image of it that
overlaps them and is not staged yet is encoded: ONE tower program an image,
by the bucket of its patches (:data:`PATCH_BUCKETS`, the padding masked),
under the span ``serving.tower``, its patches taken from the worker there
(``images_cut``: a wait that is by then long over, and never made for an
image the match spared), its rows written at the image's own
prompt rows of the engine's STAGE, ``[cache_len + a bucket's rows, hidden]``
on the device (one request prefills at a time, so one stage serves all).
The prefill program slices its rows out of the stage and takes them where
its ids are ``image_token_id``.
"""

from __future__ import annotations

import collections
import hashlib
import threading
import time
from concurrent.futures import Future, wait

import jax
import jax.numpy as jnp
import numpy as np

from fleetx_tpu.obs.tracing import span
from fleetx_tpu.utils.log import logger

__all__ = ["PATCH_BUCKETS", "Tower", "layout", "outline", "row_positions",
           "trie_keys"]

# patches of one tower program (an image of 448-896 pixels a side at patch
# 14 has 1,024-4,096)
PATCH_BUCKETS = (1024, 4096)
_MASK62 = (1 << 62) - 1
_GOLD = 0x9E3779B97F4A7C15
# what the step loop gives the worker for one image's patches before it
# calls the step failed (the cut of a page is under a millisecond)
_CUT_TIMEOUT_S = 60.0
# and what a step of an engine with no lane to tick waits for a head's keys
_IDLE_WAIT_S = 0.05


def image_digest(image: np.ndarray) -> int:
    """64 bits of an image: the blake2b of its bytes and its shape (the
    hash is fed the array's own buffer: no copy of its bytes)."""
    hashed = hashlib.blake2b(np.ascontiguousarray(image).data, digest_size=8)
    hashed.update(np.asarray(image.shape, np.int64).tobytes())
    return int.from_bytes(hashed.digest(), "little")


def row_keys(digests, rows) -> np.ndarray:
    """The trie keys of the rows of images, image after image (``rows`` of
    them each): negative int64, a function of the image's digest and the
    row's place in it. All of a prompt's in one pass: the worker thread
    takes the interpreter's lock a few times a request for them, not a
    few times an image."""
    rows = np.asarray(rows, np.int64)
    first = np.cumsum(rows) - rows
    place = (np.arange(rows.sum(), dtype=np.int64)
             - np.repeat(first, rows)).astype(np.uint64)
    mixed = ((np.repeat(np.asarray(digests, np.uint64), rows)
              + place * np.uint64(_GOLD)) & np.uint64(_MASK62))  # (wraps)
    return -1 - mixed.astype(np.int64)


def outline(prompt: np.ndarray, images, group: dict):
    """``(keys, positions, rope_delta, records, images)`` of a prompt with
    images as far as its ids and the images' SHAPES say (module docstring
    "On which thread"): ``keys`` hold the text rows' alone, ``records`` one
    ``{"start", "grid"}`` an image, ``images`` the arrays in order. Every
    refusal of a prompt with images is raised here; no byte of an image is
    read. ``group`` is the configuration's ``vision`` group."""
    from fleetx_tpu.models.vision.vit import output_grid

    patch, merge = group["patch_size"], group["merge"]
    token = group["image_token_id"]
    marked = prompt == token
    keys = prompt.astype(np.int64)
    positions = np.empty((3, len(prompt)), np.int32)
    edges = np.flatnonzero(np.diff(np.concatenate(
        [[False], marked, [False]]).astype(np.int8)))
    runs = list(zip(edges[::2], edges[1::2]))        # [begin, end) each
    images = [np.asarray(image) for image in images or ()]
    records, n, at, taken = [], 0, 0, 0
    for begin, end in runs:
        text = np.arange(begin - at, dtype=np.int32)
        positions[:, at:begin] = n + text
        n += begin - at
        at = begin
        while at < end:
            if taken == len(images):
                raise ValueError(
                    f"the prompt marks rows [{at}, {end}) with image_token_id "
                    f"{token} and no image is left for them "
                    f"({len(images)} given)")
            image = images[taken]
            if image.ndim != 3 or image.dtype != np.uint8:
                raise ValueError(
                    f"image {taken}: uint8 [height, width, channels], got "
                    f"{image.dtype} {image.shape}")
            h, w = output_grid(image.shape, patch, merge)
            patches = h * w * merge ** 2
            if patches > PATCH_BUCKETS[-1]:
                raise ValueError(
                    f"image {taken} has {patches} patches; a tower "
                    f"program takes at most {PATCH_BUCKETS[-1]}")
            if at + h * w > end:
                raise ValueError(
                    f"image {taken} makes {h} x {w} = {h * w} rows and the "
                    f"run of image_token_id at row {at} has {end - at} left")
            r, c = np.divmod(np.arange(h * w, dtype=np.int32), w)
            positions[:, at:at + h * w] = n + np.stack(
                [np.zeros_like(r), r, c])
            records.append({"start": int(at), "grid": (h * merge, w * merge)})
            n += max(h, w)
            at += h * w
            taken += 1
    if taken != len(images):
        raise ValueError(f"{len(images)} images given and the prompt's runs "
                         f"of image_token_id {token} take {taken}")
    positions[:, at:] = n + np.arange(len(prompt) - at, dtype=np.int32)
    n += len(prompt) - at
    return keys, positions, int(n - len(prompt)), records, images


def _image_rows(record: dict, merge: int) -> slice:
    """The prompt rows of the image of ``record``."""
    rows = record["grid"][0] * record["grid"][1] // merge ** 2
    return slice(record["start"], record["start"] + rows)


def _key_rows(keys: np.ndarray, records, images, group: dict) -> None:
    """Stage one of the bytes' work: the images' rows of ``keys`` (every
    row that holds ``image_token_id`` is an image's, in order)."""
    rows = [_image_rows(record, group["merge"]) for record in records]
    keys[keys == group["image_token_id"]] = row_keys(
        [image_digest(image) for image in images],
        [image.stop - image.start for image in rows])


def layout(prompt: np.ndarray, images, group: dict):
    """``(keys, positions, rope_delta, records)`` of a prompt with images
    (module docstring), whole, on the calling thread; ``records``: one
    ``{"start", "grid", "patches"}`` an image. ``group`` is the
    configuration's ``vision`` group."""
    from fleetx_tpu.models.vision.vit import image_patches

    keys, positions, delta, records, images = outline(prompt, images, group)
    _key_rows(keys, records, images, group)
    for record, image in zip(records, images):
        record["patches"] = image_patches(
            image, group["patch_size"], group["merge"])
    return keys, positions, delta, records


def row_positions(req, start: int, n: int) -> np.ndarray:
    """``[3, n]`` int32: the rotary positions of rows ``[start, start + n)``
    of a request: its prompt's as laid out at submit, a decoded row's its
    cache row plus ``rope_delta``."""
    rows = np.arange(start, start + n, dtype=np.int32)
    out = np.broadcast_to(rows + np.int32(req.rope_delta), (3, n)).copy()
    if req.positions is not None:
        inside = rows < req.prompt_len
        out[:, inside] = req.positions[:, rows[inside]]
    return out


def trie_keys(req, tokens):
    """What the prefix trie takes for ``tokens`` (the request's prompt, or
    its prompt and the tokens it has emitted) where the prompt has
    ``keys``: those, and an emitted token its id."""
    return np.concatenate([req.keys[:len(tokens)], np.asarray(
        tokens[len(req.keys):], np.int64)])


class _Worker:
    """A single-worker executor whose thread lives only while it has work:
    ``submit`` starts one where none runs, and the thread ends when it
    finds nothing queued, so an engine at rest (shut down, drained or
    merely idle) has no thread, and nothing has to close it."""

    def __init__(self, name: str):
        self._name = name
        self._lock = threading.Lock()
        self._jobs: collections.deque = collections.deque()
        self._thread = None

    def submit(self, fn, *args) -> Future:
        """``fn(*args)`` behind everything handed over before it."""
        future = Future()
        with self._lock:
            self._jobs.append((future, fn, args))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name=self._name, daemon=True)
                self._thread.start()
        return future

    def _run(self) -> None:
        while True:
            with self._lock:
                if not self._jobs:
                    self._thread = None
                    return
                future, fn, args = self._jobs.popleft()
            if not future.set_running_or_notify_cancel():
                continue
            try:
                future.set_result(fn(*args))
            except BaseException as exc:  # noqa: BLE001 - the future's to carry
                future.set_exception(exc)

    def join(self, timeout: float) -> bool:
        """Wait up to ``timeout`` s for the thread to run out of work;
        whether none is alive."""
        thread = self._thread
        if thread is not None:
            thread.join(timeout)
        return self._thread is None


class Tower:
    """The vision tower's programs, the stage their rows wait in, and the
    worker that reads the images' bytes (module docstring). ``engine`` is
    held for its model, params, metrics, mesh context and program
    counter."""

    def __init__(self, engine):
        from fleetx_tpu.models.vision.vit import tower_of

        cfg = engine.model.cfg
        self.engine = engine
        self.module = tower_of(cfg)
        self.group = cfg.vision_fields
        self.merge = cfg.vision_fields["merge"]
        self.image_token_id = cfg.vision_fields["image_token_id"]
        self._jits = {}
        self.worker = _Worker(f"fleetx-rows-in-{engine.metrics.engine_label}")
        self.reset()

    def outline(self, prompt: np.ndarray, images):
        """:func:`outline` under this tower's group: inside ``submit``."""
        return outline(prompt, images, self.group)

    def lay_out(self, req, outlined) -> None:
        """``req`` takes what ``submit`` outlined and the worker takes its
        images: the digests, after which ``req.keys`` is whole and
        ``req.keyed`` done; then each image's patches, the future in its
        record."""
        from fleetx_tpu.models.vision.vit import image_patches

        keys, req.positions, req.rope_delta, req.images, images = outlined
        req.keys, submitted = keys, time.perf_counter()

        def key():
            _key_rows(keys, req.images, images, self.group)
            self.engine.metrics.observe_layout(
                time.perf_counter() - submitted)

        req.keyed = self.worker.submit(key)
        for record, image in zip(req.images, images):
            record["patches"] = self.worker.submit(
                image_patches, image, self.group["patch_size"], self.merge)

    def keyed(self, req) -> bool:
        """Whether ``req``, the head of the queue, has its keys. One that
        has not blocks admission this step (``layout_blocked_steps``) and
        the step goes on to its tick; an engine with no lane to tick waits
        for the worker here, a while, where it would else spin. A request
        whose images the worker could not read ends
        ``finish_reason="error"``."""
        engine, keyed = self.engine, req.keyed
        if (not keyed.done() and not engine._active
                and engine._inflight is None):
            wait([keyed], timeout=_IDLE_WAIT_S)
        if not keyed.done():
            engine.metrics.record_layout_blocked()
            return False
        failed = keyed.exception()
        if failed is None:
            return True
        logger.error("serving: request %d ends: its images could not be "
                     "read (%r)", req.id, failed)
        engine.scheduler.remove(req.id)
        engine._finalize(req, "error", engine._now())
        return False

    def reset(self) -> None:
        """A fresh stage (construction, and recovery: a failed program may
        have taken the old one with it)."""
        engine, cfg = self.engine, self.engine.model.cfg
        rows = engine.cache_len + PATCH_BUCKETS[-1] // self.merge ** 2
        self.stage = engine._replicate(
            jnp.zeros((rows, cfg.hidden_size), cfg.dtype))

    def _program(self, bucket: int):
        fn = self._jits.get(bucket)
        if fn is not None:
            return fn
        engine, module = self.engine, self.module
        out_rows = bucket // self.merge ** 2

        def run(params, stage, pixels, ints):
            # ints: the image's rows and columns of patches, its first row
            with jax.named_scope("tower"):
                patches = pixels.astype(jnp.float32) / 127.5 - 1.0
                rows = module.apply(
                    {"params": engine._dequant_params(params)["vision"]},
                    patches, ints[:2])
                real = (jnp.arange(out_rows)[:, None]
                        < ints[0] * ints[1] // self.merge ** 2)
                held = jax.lax.dynamic_slice_in_dim(stage, ints[2], out_rows)
                return jax.lax.dynamic_update_slice_in_dim(
                    stage, jnp.where(real, rows.astype(stage.dtype), held),
                    ints[2], 0)

        fn = self._jits[bucket] = jax.jit(
            run, donate_argnums=(1,) if engine._donate_cache else ())
        return fn

    def stage_rows(self, req, start: int, n: int) -> None:
        """Encode every image of ``req`` that overlaps rows ``[start, start
        + n)`` and is not staged yet, in order."""
        engine = self.engine
        for number, image in enumerate(req.images):
            rows = _image_rows(image, self.merge)
            if (number in req.staged or rows.start >= start + n
                    or rows.stop <= start):
                continue
            cut = image["patches"]
            if isinstance(cut, Future):     # the worker's (lay_out)
                cut = cut.result(timeout=_CUT_TIMEOUT_S)
                engine.metrics.record_cut()
            patches = len(cut)
            bucket = next(b for b in PATCH_BUCKETS if b >= patches)
            first = bucket not in self._jits
            fn = self._program(bucket)
            program = engine._next_program()
            with span("serving.tower", request=req.id, image=number,
                      patches=patches, bucket=bucket, program=program) as at:
                if first:
                    at["first"] = True
                pixels = np.zeros((bucket, cut.shape[1]), np.uint8)
                pixels[:patches] = cut
                ints = np.asarray([*image["grid"], image["start"]], np.int32)
                with engine._mesh_context():
                    self.stage = fn(engine.params, self.stage,
                                    jnp.asarray(pixels), jnp.asarray(ints))
            req.staged.add(number)
            engine.metrics.record_tower(patches)
            engine._carried["tower"] += 1

    def skipped(self, req, shared: int) -> int:
        """Images of ``req`` that lie wholly inside a match of ``shared``
        rows: the tower never sees them."""
        return sum(_image_rows(image, self.merge).stop <= shared
                   for image in req.images)
