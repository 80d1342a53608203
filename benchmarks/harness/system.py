"""The system under test, as the benchmark holds it: the engine's
``Config`` from a configuration file, an in-process ``serve.Server`` on a
thread, and its counters and timers read from the StatementLog's registry.
From the program come only these and its compile events; what is made of
them is the readers' (``layer_metrics/``).
"""

from __future__ import annotations

import os

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = {"programs": 0}
_listening = False


def _on_duration(event: str, _seconds: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        _compiles["programs"] += 1


def listen_for_compiles() -> None:
    """Count every program this process hands to the compiler (a hit in
    the persistent cache included: it still costs a load inside a
    window). The engine's own ``compiles`` counter does not move on the
    tiled path."""
    global _listening
    import jax

    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def compiled_programs() -> int:
    """Programs handed to the compiler by this process so far."""
    return _compiles["programs"]


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no such statistic: the CPU rehearsal)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def engine_config(config: dict, store_root: str, shrink: float = 1.0):
    """The engine's ``Config`` a configuration file describes.
    ``shrink`` (rehearsals only) scales every ``*_bytes`` override with
    the data, so a cell that tiles at its own scale tiles at a tiny one."""
    from cloudberry_tpu.config import Config

    eng = config["engine"]
    over = {k: (max(int(v * shrink), 1 << 20)
                if k.endswith("_bytes") and shrink != 1.0 else v)
            for k, v in eng.get("overrides", {}).items()}
    over["storage.root"] = store_root
    return Config(n_segments=int(eng["n_segments"])).with_overrides(**over)


def snapshot(log) -> dict:
    """Counters and histogram (count, sum) pairs at one moment."""
    snap = log.registry.snapshot()
    return {"counters": dict(snap["counters"]),
            "hists": {k: (h["count"], h["sum"])
                      for k, h in snap["histograms"].items()},
            "jax_compiles": dict(_compiles)}


def enable_compile_cache() -> str:
    """The engine's placement rule: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else the fixed ``<checkout>/.jax_cache``."""
    from cloudberry_tpu.utils import compilecache

    path = compilecache.enable_compile_cache()
    os.makedirs(path, exist_ok=True)
    return path
