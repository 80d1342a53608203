"""Keep the module text of the programs a statement launches: shared by
tests/program_identity_worker.py (a process of its own) and
tests/test_lowerer.py. Imports nothing of JAX or the engine, so the worker
may import it before it sets the platform."""

from __future__ import annotations

import functools


def recording(fn, sink: list, once: bool = False):
    """``fn`` (a jitted program) appending ``(function name, lowered module
    text)`` to ``sink`` at every launch, or at its first only (a tiled step
    is launched once a tile)."""
    seen = []

    @functools.wraps(fn)    # a distributed program's byte counts ride on it
    def call(*args):
        if not (once and seen):
            seen.append(True)
            sink.append((getattr(fn, "__name__", "?"),
                         fn.lower(*args).as_text()))
        return fn(*args)
    call.recorded = True
    return call


def record_tiled_programs(modules, sink: list, set_attr=setattr) -> None:
    """Every tiled executable class of ``modules`` (exec/tiled.py) that
    builds programs in a ``_compile`` of its own hands them out
    recording, at their first launch. ``set_attr``: a
    test's ``monkeypatch.setattr``."""
    def recording_compile(compile_):
        def _compile(self):
            progs = compile_(self)
            if not all(getattr(p, "recorded", False) for p in progs):
                self._compiled = progs = tuple(
                    recording(p, sink, once=True) for p in progs)
            return progs
        return _compile

    for cls in {c for m in modules for c in vars(m).values()
                if isinstance(c, type) and "_compile" in vars(c)}:
        set_attr(cls, "_compile", recording_compile(vars(cls)["_compile"]))
