"""Small shared helpers (reference: helper/ package family)."""
from __future__ import annotations

import os

from nomad_tpu import knobs


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache so a fresh process
    deserializes the placement-kernel variant grid instead of
    recompiling it.  The reference keeps scheduler workers hot at
    leadership (nomad/worker.go); for an XLA-compiled scheduler the
    equivalent serving-readiness lever is a persistent compile cache +
    AOT warmup.

    The directory is placed from OUTSIDE: when `JAX_COMPILATION_CACHE_DIR`
    is set JAX has already read it and nothing here touches
    `jax_compilation_cache_dir`; unset, the cache lives at the fixed
    `<checkout>/.jax_cache` (the path is part of the cache key, so it
    must not move between runs).  NOMAD_TPU_JAX_CACHE=0 disables it
    (tests do: a CPU test run must not share AOT executables across
    hosts).  Returns the directory in use (None when disabled); errors
    propagate — a cache that silently fails to engage costs a full cold
    compile of the variant grid on every start."""
    if not knobs.get_bool("NOMAD_TPU_JAX_CACHE"):
        return None
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def requires_lock(lockname: str = "_lock"):
    """Marker decorator: the decorated method must only be called with
    `lockname` already held by the caller.  Runtime no-op; the static
    lock-discipline checker (nomad_tpu.analysis) treats the body as
    lock-covered and every caller remains obligated to hold the lock at
    the call site."""
    def mark(fn):
        fn.__requires_lock__ = lockname
        return fn
    return mark


def generate_uuid() -> str:
    """RFC-4122-shaped random id, ~10x faster than uuid.uuid4() (which
    dominates profiles at thousands of allocs/evals per second; the
    reference's helper/uuid/uuid.go does exactly this — raw random bytes
    formatted with dashes)."""
    h = os.urandom(16).hex()
    return f"{h[:8]}-{h[8:12]}-{h[12:16]}-{h[16:20]}-{h[20:]}"
