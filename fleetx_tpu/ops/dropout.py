"""Counter-hash dropout: the TPU-cheap replacement for per-element threefry.

``flax.linen.Dropout`` draws its keep mask with ``jax.random.bernoulli``,
which on TPU lowers to a threefry2x32 keystream — ~100 VPU ops per pair of
random words. For the GPT hidden dropouts (2 per layer on [b, s, h]
activations, reference single_model.py:291,451 dropout1/dropout2) that RNG
was about 12% of the 345M train step on v5e in an earlier round's A/B
(19,907 tok/s with hidden dropout off vs 18,112 on; not reproduced).

``HashDropout`` keeps the same contract — deterministic given the
``'dropout'`` PRNG key, scale-by-1/(1-rate), zero where dropped — but
derives the per-element keep decision from the lowbias32 integer hash the
flash-attention kernel already uses for attention dropout
(fleetx_tpu/ops/pallas/flash_attention.py::dropout_keep_scale): ONE
threefry call per module call folds the key into an int32 seed, then each
element costs ~13 int32 VPU ops. The hash path is pure jnp, so it runs
identically on CPU tests and TPU, and autodiff flows through the multiply
(the mask itself is an integer computation with no gradient path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from fleetx_tpu.ops.pallas.flash_attention import dropout_keep_scale

__all__ = ["HashDropout", "dropout_layer"]


def dropout_layer(rate: float, name: str, fast: bool = True) -> nn.Module:
    """The one place models pick their hidden-dropout implementation:
    hash-based by default; ``fast=False`` (the per-family ``fast_dropout``
    config field) restores flax's threefry ``nn.Dropout`` as a rollback."""
    if fast:
        return HashDropout(rate, name=name)
    return nn.Dropout(rate, name=name)


class HashDropout(nn.Module):
    """Drop-in replacement for ``nn.Dropout`` (broadcast_dims unsupported).

    rate: drop probability. rng_collection: PRNG collection name, default
    ``'dropout'`` — same key => same mask, so trainers that derive
    per-data-rank dropout keys (parallel/env.py) keep mp-invariance.
    """

    rate: float
    rng_collection: str = "dropout"

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        if deterministic or self.rate == 0.0:
            return x
        if self.rate >= 1.0:
            return jnp.zeros_like(x)
        rng = self.make_rng(self.rng_collection)
        # one threefry draw per call (not per element): fold the key to the
        # int32 counter-hash seed
        seed = jax.random.bits(rng, (), "uint32").astype(jnp.int32)
        # element index as the hash counter; int32 covers activations up to
        # 2^31 elements (a [32, 2048, 12288] GPT-175B microbatch is 8e8)
        if x.size >= (1 << 31):
            raise ValueError(
                f"HashDropout supports < 2^31 elements per call; got shape "
                f"{x.shape} ({x.size}). Split the activation or use "
                f"fast_dropout=False."
            )
        idx = jax.lax.iota(jnp.int32, x.size).reshape(x.shape)
        scale = dropout_keep_scale(seed, jnp.int32(0), idx, jnp.int32(0),
                                   self.rate)
        return x * scale.astype(x.dtype)
