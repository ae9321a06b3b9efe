"""KV-free embedding / classification engine for vision models.

The simplest engine the protocol admits: a ViT (or MoCo encoder built
on one) maps a batch of images to pooled features or class logits in a
single forward, so serving is pure request coalescing — a
:class:`~fleetx_tpu.serving.batch_engine.BatchingEngine` whose batches
are stacks of fixed-shape images. Two modes, keyed off the model
config exactly like ``fleetx_tpu/models/vision/vit.py`` itself:

- ``cfg.num_classes == 0`` → **embedding**: the pooled hidden vector
  per image, emitted as its float32 bits bit-cast to int32 tokens
  (lossless — :func:`decode_floats` inverts it). Riding the int32
  token channel keeps router migration/history byte-parity semantics
  intact for vectors: the "tokens" ARE the embedding.
- ``cfg.num_classes > 0`` → **classification**: one token, the argmax
  class id.

The wire format for inputs mirrors the outputs: a request "prompt" is
one image, channels-last ``[H, W, C]`` float32, flattened and bit-cast
to int32 (:func:`encode_floats`) — exactly ``H*W*C`` elements, which
is what ``_validate`` enforces (and what makes cross-model dispatch
mistakes fail loudly: a text prompt is never the right size). Every
image is the same shape and every batch is padded to ``slots`` rows, so
there is exactly ONE jitted program: a request's bytes do not depend on
how many rode beside it (two batch shapes are two programs, and XLA
may round them differently in the last bits). docs/SERVING.md
"Heterogeneous fleet".
"""

from __future__ import annotations

from typing import List

import jax
import numpy as np

from fleetx_tpu.serving.batch_engine import BatchingEngine
from fleetx_tpu.serving.model_protocol import ModelCapabilities

__all__ = ["EmbeddingEngine", "decode_floats", "encode_floats"]


def encode_floats(arr) -> np.ndarray:
    """Flatten a float32 array to its int32 bit pattern — the wire
    encoding submits carry (lossless; :func:`decode_floats` inverts)."""
    return np.ascontiguousarray(
        np.asarray(arr, np.float32).reshape(-1)).view(np.int32)


def decode_floats(tokens) -> np.ndarray:
    """Invert :func:`encode_floats`: int32 wire tokens back to the flat
    float32 vector they encode."""
    return np.ascontiguousarray(
        np.asarray(tokens, np.int32).reshape(-1)).view(np.float32)


class EmbeddingEngine(BatchingEngine):
    """Dynamic-batching image embedding / classification over one
    vision model (module docstring)."""

    def __init__(self, model, variables, *, family: str = "vit", **kw):
        cfg = model.cfg
        self.image_shape = (int(cfg.image_size), int(cfg.image_size),
                            int(cfg.in_channels))
        self.image_elems = int(np.prod(self.image_shape))
        self.classify = int(cfg.num_classes) > 0
        self.capabilities = ModelCapabilities(
            family=family,
            has_kv_cache=False,
            supports_spec=False,
            cache_layout="none",
            max_input=self.image_elems,
            emits="tokens" if self.classify else "floats",
        )
        super().__init__(model, variables, **kw)

        def fwd(params, images):
            out = model.apply({"params": params}, images,
                              deterministic=True)
            return jax.numpy.argmax(out, axis=-1) if self.classify else out

        self._fwd = jax.jit(fwd)

    def _validate(self, prompt: np.ndarray) -> None:
        if prompt.size != self.image_elems:
            raise ValueError(
                f"embedding request must be one {self.image_shape} "
                f"float32 image bit-cast to int32 ({self.image_elems} "
                f"elements, see serving.embedding_engine.encode_floats); "
                f"got {prompt.size}")

    def _run_batch(self, requests) -> List[List[int]]:
        images = np.zeros((self.slots,) + self.image_shape, np.float32)
        for i, r in enumerate(requests):
            images[i] = decode_floats(r.prompt).reshape(self.image_shape)
        out = np.asarray(self._fwd(self.params, images))
        if self.classify:
            return [[int(out[i])] for i in range(len(requests))]
        return [[int(t) for t in encode_floats(out[i])]
                for i in range(len(requests))]

    @property
    def submit_limit(self) -> int:
        """One past the exact image size — images are fixed-shape, so
        any LARGER prompt is rejected (smaller ones fail in
        ``_validate`` with the precise shape message)."""
        return self.image_elems + 1
