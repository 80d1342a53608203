"""JAX's persistent compilation cache, placeable from outside.

Compiling is the cost of a cold run on the chip: every sort in a program
costs the TPU compiler half a minute or more whatever the row count
(ROADMAP S10), and a fresh process starts with no compiled code. The
entry points (``chip_smoke.py``, ``bench.py``, ``python -m cloudberry_tpu
serve``, the measuring tools) call :func:`enable_compile_cache` once,
before their first compile. Tests do not: they compile thousands of
small CPU programs and have no use for a cache that outlives them.

Placement rule (on-chip guide §1): where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX itself reads it and this module sets no directory; where it
is not, the cache lives at ONE fixed path inside the checkout — the path
is part of the cache key, so a directory that moves never hits.
"""

from __future__ import annotations

import os

_DEFAULT_DIRNAME = ".jax_cache"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` (git-ignored)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, _DEFAULT_DIRNAME)


# process-wide hit/miss counts, fed by JAX's own monitoring events
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _counts[key] += 1


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory. Every program is kept, however fast it compiled: a served
    statement launches dozens of small eager programs next to its one
    big one, and a warm process should compile none of them."""
    global _listening
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = default_cache_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def entry_banner() -> str:
    """What every measuring entry point prints first: the device JAX runs
    on and where compiles are kept (turning the cache on on the way)."""
    from cloudberry_tpu.parallel.mesh import device_line

    return (f"device: {device_line()}; "
            f"compile cache {enable_compile_cache()}")


def cache_counts() -> dict:
    """``{"hits": n, "misses": n}`` since :func:`enable_compile_cache`."""
    return dict(_counts)


def cache_entries(path: str) -> int:
    """Number of cached executables under ``path`` (0 when absent)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
