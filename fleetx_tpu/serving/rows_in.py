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

**At admission** the trie is matched by ``keys`` BEFORE any tower call: an
image wholly inside the match is neither encoded nor prefilled.

**In the step's prefill slot** (:class:`Tower`). Before a prefill program
takes rows ``[start, start + n)`` of a request, every image of it that
overlaps them and is not staged yet is encoded: ONE tower program an image,
by the bucket of its patches (:data:`PATCH_BUCKETS`, the padding masked),
under the span ``serving.tower``, its rows written at the image's own
prompt rows of the engine's STAGE, ``[cache_len + a bucket's rows, hidden]``
on the device (one request prefills at a time, so one stage serves all).
The prefill program slices its rows out of the stage and takes them where
its ids are ``image_token_id``.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from fleetx_tpu.obs.tracing import span

__all__ = ["PATCH_BUCKETS", "Tower", "layout", "row_positions", "trie_keys"]

# patches of one tower program (an image of 448-896 pixels a side at patch
# 14 has 1,024-4,096)
PATCH_BUCKETS = (1024, 4096)
_MASK62 = (1 << 62) - 1
_GOLD = 0x9E3779B97F4A7C15


def image_keys(image: np.ndarray, rows: int) -> np.ndarray:
    """The trie keys of an image's ``rows`` rows: negative int64, a
    function of the image's bytes, its shape and the row's place."""
    digest = hashlib.blake2b(
        np.ascontiguousarray(image).tobytes()
        + np.asarray(image.shape, np.int64).tobytes(), digest_size=8).digest()
    base = int.from_bytes(digest, "little")
    place = np.arange(rows, dtype=np.uint64) * np.uint64(_GOLD)
    mixed = (np.uint64(base) + place) & np.uint64(_MASK62)   # (wraps: uint64)
    return -1 - mixed.astype(np.int64)


def layout(prompt: np.ndarray, images, group: dict):
    """``(keys, positions, rope_delta, records)`` of a prompt with images
    (module docstring); ``records``: one ``{"start", "grid", "patches"}`` an
    image. ``group`` is the configuration's ``vision`` group."""
    from fleetx_tpu.models.vision.vit import image_patches

    patch, merge = group["patch_size"], group["merge"]
    token = group["image_token_id"]
    marked = prompt == token
    keys = prompt.astype(np.int64)
    positions = np.empty((3, len(prompt)), np.int32)
    edges = np.flatnonzero(np.diff(np.concatenate(
        [[False], marked, [False]]).astype(np.int8)))
    runs = list(zip(edges[::2], edges[1::2]))        # [begin, end) each
    images = list(images or ())
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
            image = np.asarray(images[taken])
            if image.ndim != 3 or image.dtype != np.uint8:
                raise ValueError(
                    f"image {taken}: uint8 [height, width, channels], got "
                    f"{image.dtype} {image.shape}")
            pixels = image_patches(image, patch, merge)
            h, w = (image.shape[0] // (patch * merge),
                    image.shape[1] // (patch * merge))
            if len(pixels) > PATCH_BUCKETS[-1]:
                raise ValueError(
                    f"image {taken} has {len(pixels)} patches; a tower "
                    f"program takes at most {PATCH_BUCKETS[-1]}")
            if at + h * w > end:
                raise ValueError(
                    f"image {taken} makes {h} x {w} = {h * w} rows and the "
                    f"run of image_token_id at row {at} has {end - at} left")
            r, c = np.divmod(np.arange(h * w, dtype=np.int32), w)
            positions[:, at:at + h * w] = n + np.stack(
                [np.zeros_like(r), r, c])
            keys[at:at + h * w] = image_keys(image, h * w)
            records.append({"start": int(at), "grid": (h * merge, w * merge),
                            "patches": pixels})
            n += max(h, w)
            at += h * w
            taken += 1
    if taken != len(images):
        raise ValueError(f"{len(images)} images given and the prompt's runs "
                         f"of image_token_id {token} take {taken}")
    positions[:, at:] = n + np.arange(len(prompt) - at, dtype=np.int32)
    n += len(prompt) - at
    return keys, positions, int(n - len(prompt)), records


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


class Tower:
    """The vision tower's programs and the stage their rows wait in (module
    docstring). ``engine`` is held for its model, params, metrics, mesh
    context and program counter."""

    def __init__(self, engine):
        from fleetx_tpu.models.vision.vit import tower_of

        cfg = engine.model.cfg
        self.engine = engine
        self.module = tower_of(cfg)
        self.merge = cfg.vision_fields["merge"]
        self.image_token_id = cfg.vision_fields["image_token_id"]
        self._jits = {}
        self.reset()

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
            rows = image["grid"][0] * image["grid"][1] // self.merge ** 2
            if (number in req.staged or image["start"] >= start + n
                    or image["start"] + rows <= start):
                continue
            patches = len(image["patches"])
            bucket = next(b for b in PATCH_BUCKETS if b >= patches)
            first = bucket not in self._jits
            fn = self._program(bucket)
            program = engine._next_program()
            with span("serving.tower", request=req.id, image=number,
                      patches=patches, bucket=bucket, program=program) as at:
                if first:
                    at["first"] = True
                pixels = np.zeros((bucket, image["patches"].shape[1]),
                                  np.uint8)
                pixels[:patches] = image["patches"]
                ints = np.asarray([*image["grid"], image["start"]], np.int32)
                with engine._mesh_context():
                    self.stage = fn(engine.params, self.stage,
                                    jnp.asarray(pixels), jnp.asarray(ints))
            req.staged.add(number)
            engine.metrics.record_tower(patches)

    def skipped(self, req, shared: int) -> int:
        """Images of ``req`` that lie wholly inside a match of ``shared``
        rows: the tower never sees them."""
        return sum(
            image["start"] + image["grid"][0] * image["grid"][1]
            // self.merge ** 2 <= shared for image in req.images)
