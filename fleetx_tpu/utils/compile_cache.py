"""One place for JAX's persistent compilation cache.

Every entry point that compiles calls :func:`enable_compile_cache` before
its first jit: ``tools/train.py``, ``eval.py``, ``export.py``,
``inference.py``, the ``serve.py`` replica worker, the drivers of
``perfbench/`` and ``chip_smoke.py``. The directory's path is part
of the cache's key, so a directory that moves between runs never hits;
there are exactly two places it can be:

- where ``JAX_COMPILATION_CACHE_DIR`` says, when it is set: jax reads
  that variable itself, and this module then sets NO directory in code
  (a machine that provides a cache across runs names it this way);
- otherwise ``.jax_cache/`` at the root of the checkout (gitignored) —
  a fixed path, never a temporary name, a pid or a timestamp, so a second
  run of the same program on the same machine hits what the first wrote.
  This default applies on an accelerator only: on the CPU backend
  compiles are test-sized, and XLA:CPU's loader logs a page-long
  machine-feature warning for every entry it reads back.

Child processes inherit the same place: the environment variable passes
down by itself, and the fixed path is the same for every process started
from this checkout.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns its directory
    (None on a CPU run without ``JAX_COMPILATION_CACHE_DIR``). Asks jax for
    its backend, so call it after everything that must precede backend
    initialization (utils/xla_flags.py, ``init_dist_env``).

    Also drops jax's size/time floors so every program is kept — the
    small per-bucket serving jits are many, and re-compiling each of them
    cold is what a chip run would otherwise pay on every call — and makes
    op_names and source lines part of the key (below).

    Whatever the backend, the first call also puts the program's listener
    on jax's compile events (``fleetx_tpu/obs/compiles.py``: every trace,
    lowering, compile and cache load from here on is a span with its
    program's name, and seconds on a counter)."""
    import jax

    from fleetx_tpu.obs import compiles

    compiles.install()

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        if jax.default_backend() == "cpu":
            return None
        cache_dir = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # jax leaves op_names and source lines out of the cache's key, so a
    # program that differs from a cached one in its scopes alone comes back
    # with the OLD names: a device trace then books its time to scopes of a
    # build that no longer exists (seen on the chip, PR 23: prefill programs
    # cached by the parent had none of this build's scopes). The names are
    # what the device-trace parts are read from, so they belong in the key.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # (The key then holds the Python call stack of every instruction too,
    # so one program reached through two callers is two entries. Leaving
    # the stacks out, jax_include_full_tracebacks_in_locations=False, is
    # no way around it: a Pallas call then loses its name, and the trace
    # its kernel families; v5e compile, PR 23.)
    return cache_dir
