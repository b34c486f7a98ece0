"""Persistent XLA compilation cache.

The scheduler's solvers are jitted per shape bucket; a cold process pays
10-40 s of XLA compile per bucket, which is the dominant wall-clock cost
of small workloads (a 500-pod SchedulingBasic run spends ~95% of its
wall time compiling).  The reference has no analogue — Go compiles ahead
of time — so to compete on wall clock the executables must survive the
process: JAX's persistent compilation cache serializes every compiled
program to disk keyed by (HLO, compile options, platform version), and
later processes deserialize in milliseconds instead of recompiling.

Enabled on import of kubernetes_tpu.ops.  The directory is placed from
outside: where JAX already has one (``JAX_COMPILATION_CACHE_DIR``, or a
``jax.config.update`` by the embedding program) it is kept and this
module sets none; otherwise the cache is ``<checkout>/.jax_cache``,
derived from this package's location so it never depends on the
working directory or ``$HOME``.  JAX's own switch turns it off
(``JAX_ENABLE_COMPILATION_CACHE=false``).

Reference framing: this plays the role the reference's ahead-of-time
compilation plays — scheduling code is ready the moment the binary
starts (cmd/kube-scheduler is a compiled Go binary; our "binary" is the
jax cache + the Python package).
"""

from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable() -> str | None:
    """Turn on JAX's persistent compilation cache and return its
    directory (None when JAX's own switch has it off).  Idempotent.
    Every compile is cached (min-time/min-size gates zeroed): even
    100 ms executables are worth never recompiling, and the scheduler's
    shape-bucket family is small enough that cache size is not a
    concern.

    A default directory that cannot be created or written raises: a
    process that silently compiles everything cold looks healthy and is
    not.  A directory given from outside is JAX's to validate (it may
    be a remote URL), so it is returned untouched."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    outside = jax.config.jax_compilation_cache_dir
    if outside:
        return outside
    try:
        os.makedirs(CHECKOUT_CACHE_DIR, exist_ok=True)
        if not os.access(CHECKOUT_CACHE_DIR, os.W_OK | os.X_OK):
            raise PermissionError(13, "not writable", CHECKOUT_CACHE_DIR)
    except OSError as e:
        raise OSError(
            f"compile cache directory {CHECKOUT_CACHE_DIR} is unusable "
            f"({e}); set JAX_COMPILATION_CACHE_DIR to a writable one, or "
            "JAX_ENABLE_COMPILATION_CACHE=false to run uncached"
        ) from e
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
