"""Process-wide shared cache tier — compiled programs across sessions.

Until now every Session owned private LRUs for the three expensive
reusable artifacts: generic plans (sched/paramplan.py — skeleton →
compiled program with literals as device inputs), capacity-rung
executables (session._rung_cache — one SPMD program per motion-rung
signature), and join indexes (exec/joinindex.py — host-mirrored
sorted-build scaffolding). A server running per-connection backends over
a durable store therefore recompiled every skeleton once PER TENANT even
though the programs are identical.

This module promotes those caches to an engine-wide tier organized as
invalidation SCOPES:

- sessions over the same durable store root share ONE scope — tenant B
  re-binds tenant A's compiled skeleton with zero recompiles;
- storeless sessions get a private scope (their table contents have no
  cross-session identity), which preserves the exact pre-tier behavior.

The invalidation contract is the existing signature discipline, not a
new protocol:

- every shared key embeds content-stable TABLE VERSION tokens
  (``table_key``): a store-backed table outside a transaction is pinned
  by its store version (any commit bumps it); anything else — in-RAM
  tables, mid-transaction state, the ``$dual`` constant relation — falls
  back to a process-unique table uid + local version, making those
  entries private-by-construction even inside a shared scope;
- the config OBJECT IDENTITY is the config epoch (generic plans already
  check ``config is session.config``): any with_overrides/degrade_mesh
  swap replaces the frozen tree wholesale and orphans every entry built
  under it;
- the UDF registry version stays in every plan epoch (process-wide
  state compiled into programs).

Structural guards that per-session caches got from ``catalog.ddl_version``
are covered differently per cache: generic plans carry a full structural
signature (paramplan._Walker captures everything the trace bakes), so
cross-session reuse needs no ddl counter; rung executables have no such
signature, so their shared keys stay scoped to one session's catalog
generation whenever the catalog holds views (view redefinition can change
the plan under an unchanged query text).
"""

from __future__ import annotations

import itertools
import threading
import weakref


class CacheScope:
    """One invalidation domain's caches. ``kind`` is 'store' (shared by
    every session over the same storage root) or 'session' (private)."""

    def __init__(self, kind: str, token):
        self.kind = kind
        self.token = token
        # generic-plan cache: skeleton -> [GenericPlan, ...] (paramplan)
        self.generic: dict = {}
        self.generic_lock = threading.Lock()
        # capacity-rung executables (session._rung_executable)
        self.rung: dict = {}
        self.rung_lock = threading.Lock()
        # join indexes (exec/joinindex.py)
        self.joinindex: dict = {}
        self.joinindex_lock = threading.Lock()
        # HBM-resident scan buffer pool (exec/bufferpool.py), created
        # lazily by bufferpool.pool_for — it owns its own leaf lock and
        # byte budget; anchored here so sessions over one store root
        # share residency the way they share compiled programs
        self.bufferpool = None
        # learned-stats store (plan/feedback.py), created lazily by
        # feedback.store_for — same anchoring rationale: sketches learned
        # by one session serve every session over the same store root
        self.feedback = None

    def clear(self) -> None:
        with self.generic_lock:
            self.generic.clear()
        with self.rung_lock:
            self.rung.clear()
        with self.joinindex_lock:
            self.joinindex.clear()
        pool = self.bufferpool
        if pool is not None:
            pool.clear()

    def snapshot(self) -> dict:
        out = {
            "kind": self.kind,
            "generic_skeletons": len(self.generic),
            "rung_entries": len(self.rung),
            "join_index_entries": len(self.joinindex),
        }
        pool = self.bufferpool
        if pool is not None:
            out["bufferpool"] = pool.snapshot()
        fb = self.feedback
        if fb is not None:
            out["feedback"] = fb.snapshot()
        return out


_tier_lock = threading.Lock()
_store_scopes: dict[str, CacheScope] = {}
# process-lifetime bound on retained store scopes (LRU): evicting one
# only forfeits cached programs for sessions opened LATER against that
# root — existing sessions keep their scope object, and correctness
# never depends on scope identity (keys are self-describing)
_STORE_SCOPES_MAX = 16
_uid_counter = itertools.count(1)


def scope_for(session) -> CacheScope:
    """The session's cache scope, created on first use. Store-backed
    sessions with ``config.sched.shared_cache`` share the per-root scope;
    everything else is private. Sessions cache the result
    (``session._cache_scope``) — Session.__init__ calls this once."""
    scope = getattr(session, "_cache_scope", None)
    if scope is not None:
        return scope
    if session.store is not None and session.config.sched.shared_cache:
        root = str(session.config.storage.root)
        with _tier_lock:
            scope = _store_scopes.pop(root, None)
            if scope is None:
                scope = CacheScope("store", root)
            _store_scopes[root] = scope  # LRU touch
            while len(_store_scopes) > _STORE_SCOPES_MAX:
                _store_scopes.pop(next(iter(_store_scopes)))
    else:
        scope = CacheScope("session", id(session))
    session._cache_scope = scope
    return scope


def _uid(obj) -> int:
    """Process-unique, never-reused id for a table object (or any
    object), stamped lazily — the private-key component that makes
    object-bound entries collision-free inside a shared scope (plain
    ``id()`` is reused after GC)."""
    u = getattr(obj, "_cache_uid", None)
    if u is None:
        with _tier_lock:
            u = getattr(obj, "_cache_uid", None)
            if u is None:
                u = next(_uid_counter)
                try:
                    obj._cache_uid = u
                except AttributeError:  # __slots__ or frozen: fall back
                    return id(obj)
    return u


def session_uid(session) -> int:
    return _uid(session)


_config_uids: dict[int, tuple] = {}  # id(cfg) -> (uid, weakref)


def config_uid(cfg) -> int:
    """Process-unique token for a Config OBJECT (frozen dataclasses
    reject attribute stamping, and a bare id() could be reused after
    GC): the config-epoch component for shared cache keys — programs
    bake config knobs (packed wire, ...), so entries built
    under different Config objects must never collide."""
    with _tier_lock:
        ent = _config_uids.get(id(cfg))
        if ent is not None and ent[1]() is cfg:
            return ent[0]
        u = next(_uid_counter)
        _config_uids[id(cfg)] = (u, weakref.ref(cfg))
        return u


def table_key(session, name: str):
    """Content-stable identity token for one table, suitable as a shared
    cache-key component. Raises KeyError for unknown tables (mirroring
    Session._table_versions so callers keep their existing handling)."""
    t = session.catalog.tables.get(name)
    if t is None:
        raise KeyError(name)
    scope = scope_for(session)
    if scope.kind == "session":
        # private scope: the pre-tier key (per-session dict ⇒ names
        # suffice; versions bump on every set_data/ANALYZE)
        return (name, getattr(t, "_version", 0),
                getattr(t, "_stats_version", 0))
    sv = getattr(t, "_store_version", None)
    if sv is not None and getattr(session, "_txn_snapshot", None) is None:
        # store-backed outside a transaction: the store version IS the
        # content (manifests are immutable; any commit — data, stats,
        # recreate — publishes a new version)
        return (name, "sv", sv)
    # in-RAM table / mid-transaction state: bind to this table OBJECT so
    # the entry is private even in a shared scope
    return (name, "uid", _uid(t), getattr(t, "_version", 0),
            getattr(t, "_stats_version", 0))


def table_versions(session, names):
    """Tuple of table_key tokens for a sorted name list (the shared-tier
    replacement for Session._table_versions in cache guards)."""
    return tuple(table_key(session, n) for n in names)


def topology_token(session) -> int:
    """The session's current topology-epoch id (parallel/topology.py) —
    carried by EVERY shared-tier key so a program compiled under an
    earlier epoch can never serve after a cutover, even when every
    other identity component aliases (same nseg after a failover/recover
    round trip, a reused config uid, an unchanged table version)."""
    from cloudberry_tpu.parallel.topology import topology_token as _tt

    return _tt(session)


def plan_epoch(session) -> tuple:
    """The non-table part of a generic plan's validity: the process-wide
    UDF registry version always, plus the TOPOLOGY EPOCH TOKEN (a
    cutover orphans every earlier epoch's programs); the catalog ddl
    counter only for private scopes (shared scopes rely on the full
    structural signature — ddl counters are per-catalog and would just
    block sharing)."""
    from cloudberry_tpu.exec.udf import registry_version

    scope = scope_for(session)
    if scope.kind == "session":
        return ("local", topology_token(session),
                session.catalog.ddl_version, registry_version())
    return ("store", topology_token(session), registry_version())


def rung_scope_token(session) -> tuple:
    """Key prefix for rung-executable entries. Rung programs have no
    structural signature beyond (query text, versions, motion rungs), so
    cross-session sharing is only sound when the plan is a pure function
    of store content AND config: any catalog with session-local views
    keeps its entries scoped to its own ddl generation, and the shared
    branch carries the config uid (programs bake packed-wire/...
    knobs — the config-epoch guard the sibling caches get from object
    identity)."""
    scope = scope_for(session)
    if scope.kind == "store" and not session.catalog.views:
        return ("shared", topology_token(session),
                config_uid(session.config))
    return ("cat", topology_token(session), session_uid(session),
            session.catalog.ddl_version)


def tier_snapshot(session) -> dict:
    """Observability for serve/meta.py: this session's scope."""
    scope = scope_for(session)
    out = scope.snapshot()
    out["shared"] = scope.kind == "store"
    return out
