"""Device mesh — the gp_segment_configuration analog.

The reference's cluster topology is a catalog of N segment postmasters
(cdbutil.c getCdbComponentInfo) wired by a socket interconnect
(contrib/interconnect/udp/ic_udpifc.c); here it is a jax.sharding.Mesh
with one ``seg`` axis: mesh slot ↔ segment.

Multi-host (the DCN path): each host process calls ``init_distributed``
(the gpinitsystem / interconnect-setup analog) before creating a session.
After ``jax.distributed.initialize`` the device list is GLOBAL — the
segment mesh then spans hosts, and XLA routes intra-host collectives over
ICI and inter-host collectives over DCN (Gloo on CPU test clusters) with
no change anywhere else in the engine: the executor only ever names the
``seg`` axis. Segments stay stateless (data placement is recomputed from
shared/deterministic storage), so there is no per-segment WAL to ship —
a failed host re-runs statements against pinned snapshots.

``HostTopology`` is the first-class host → segment layout (the promoted
``segments_by_host`` view): the two-level Motion path
(parallel/transport.py HierarchicalCollectives) consults it to split
every collective into an intra-host (ICI) and an inter-host (DCN) hop.
It is DERIVED state, never stored: ``host_topology`` recomputes it from
the live device list (plus any survivor restriction) on demand, so an
epoch flip — expand/shrink/failover via parallel/topology.py — re-derives
it the moment the new epoch's first plan compiles; the shared cache tier
already keys compiled programs by topology epoch, so a stale host layout
can never serve a post-cutover statement. ``CBTPU_FORCE_HOSTS=N``
partitions a single-process mesh into N simulated hosts (contiguous,
uniform) — the CPU test/bench stand-in for a real multi-host split.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass

import jax
from jax.sharding import Mesh

SEG_AXIS = "seg"

# host-topology derivation cache (device lists are stable between epoch
# flips; the key carries everything the derivation reads)
# graftlint: the lock is module-level like faultinject._lock and carries
# a witness rank (lint/config.py WITNESS_ORDER rank 4 — innermost leaf)
_topo_lock = threading.Lock()
_topo_cache: dict = {}


class DeviceRestrictionError(RuntimeError):
    """A ``device_ids`` restriction named devices the mesh cannot use.

    ``kind`` distinguishes the two failure stories:
    - ``"stale"``  — an id at or past the live device count: the id was
      plausibly valid once (before a shrink / device loss) and the caller
      is holding an out-of-date survivor list; re-probe and re-derive.
    - ``"invalid"`` — a negative or duplicate id: the restriction list
      itself is malformed, no probe will fix it.

    Before this error existed, ``segment_mesh`` silently SKIPPED
    out-of-range ids — a stale survivor list would quietly build a
    smaller mesh and every placement assumption downstream went wrong.
    """

    def __init__(self, kind: str, message: str):
        super().__init__(message)
        self.kind = kind


def init_distributed(coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> None:
    """Join (or start) a multi-host cluster. Arguments default to the
    CBTPU_COORDINATOR / CBTPU_NUM_PROCS / CBTPU_PROC_ID environment —
    this engine's gp_segment_configuration bootstrap. Idempotent; a
    single-host run (no coordinator configured) is a no-op."""
    if getattr(init_distributed, "_done", False):
        return
    coordinator = coordinator or os.environ.get("CBTPU_COORDINATOR")
    if coordinator is None:
        return
    num_processes = int(num_processes
                       or os.environ.get("CBTPU_NUM_PROCS", "1"))
    process_id = int(process_id
                     if process_id is not None
                     else os.environ.get("CBTPU_PROC_ID", "0"))
    # (XLA:CPU's cross-process collectives ride Gloo, the installed
    # jax's default jax_cpu_collectives_implementation; TPU pods' DCN
    # collectives are native.)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    init_distributed._done = True  # type: ignore[attr-defined]


def _check_device_ids(device_ids, n_devices: int) -> None:
    """The typed replacement for the old silent ``if i < len(devices)``
    skip: holes mid-list are an error the caller must see."""
    seen = set()
    for i in device_ids:
        if i < 0:
            raise DeviceRestrictionError(
                "invalid",
                f"device restriction contains negative id {i} — the "
                "restriction list is malformed, not stale")
        if i in seen:
            raise DeviceRestrictionError(
                "invalid",
                f"device restriction names id {i} twice — the "
                "restriction list is malformed, not stale")
        seen.add(i)
    stale = sorted(i for i in device_ids if i >= n_devices)
    if stale:
        raise DeviceRestrictionError(
            "stale",
            f"device restriction names id(s) {stale} but only "
            f"{n_devices} devices are visible — the ids are stale "
            "(devices lost / cluster shrunk since the restriction was "
            "derived); re-probe and rebuild the survivor list")


def device_line() -> str:
    """``platform device_kind xN`` as JAX reports it — the line every
    measuring entry point prints, so no number travels without the
    device it was taken on."""
    devices = jax.devices()
    return (f"{devices[0].platform} {devices[0].device_kind} "
            f"x{len(devices)}")


def segment_mesh(n_segments: int, device_ids=None) -> Mesh:
    """Mesh over the first n_segments GLOBAL devices (all hosts).
    ``device_ids`` restricts to surviving devices (by index into
    jax.devices()) after a probe found losses — a real loss leaves a hole
    mid-list, so the degraded mesh must skip it, not just shrink.
    A restriction naming devices that no longer exist raises the typed
    DeviceRestrictionError instead of silently building a smaller mesh."""
    devices = jax.devices()
    if device_ids is not None:
        _check_device_ids(device_ids, len(devices))
        devices = [devices[i] for i in device_ids]
    if len(devices) < n_segments:
        raise RuntimeError(
            f"config asks for {n_segments} segments but only "
            f"{len(devices)} devices are visible; for tests set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_segments}")
    chosen = devices[:n_segments]
    if jax.process_count() > 1:
        # every host must own at least one mesh segment: a host outside
        # the mesh could neither feed its shards nor read results
        owners = {int(getattr(d, "process_index", 0)) for d in chosen}
        if owners != set(range(jax.process_count())):
            raise RuntimeError(
                f"n_segments={n_segments} covers only hosts "
                f"{sorted(owners)} of {jax.process_count()}; every host "
                "must own at least one segment (raise n_segments or "
                "shrink the cluster)")
    import numpy as np

    return Mesh(np.asarray(chosen), (SEG_AXIS,))


# --------------------------------------------------------- host topology


@dataclass(frozen=True)
class HostTopology:
    """First-class host → segment layout (the promoted segments_by_host
    view of ``mesh_topology``). Immutable and DERIVED: rebuild it via
    ``host_topology`` whenever the device set may have changed (epoch
    flips do — see module docstring)."""

    n_segments: int
    # host -> tuple of global segment indices it owns (ascending)
    segs_by_host: tuple
    # True when the grouping came from CBTPU_FORCE_HOSTS (simulated
    # hosts on one process) rather than real process indices
    forced: bool = False

    @property
    def n_hosts(self) -> int:
        return len(self.segs_by_host)

    @property
    def segs_per_host(self) -> int:
        """Segments per host when UNIFORM, else 0 (the two-level path
        requires uniformity; a ragged cluster stays on flat motion)."""
        sizes = {len(s) for s in self.segs_by_host}
        return len(self.segs_by_host[0]) if len(sizes) == 1 else 0

    def host_of(self, seg: int) -> int:
        for h, segs in enumerate(self.segs_by_host):
            if seg in segs:
                return h
        raise KeyError(seg)

    def uniform_contiguous(self) -> bool:
        """True when host h owns exactly segments [h*S, (h+1)*S) — the
        layout HierarchicalCollectives' static lane algebra relies on
        (jax.devices() orders by process index, so real clusters are
        contiguous by construction; a degraded survivor restriction can
        break it, and then motion stays flat)."""
        S = self.segs_per_host
        if S == 0:
            return False
        if S * self.n_hosts != self.n_segments:
            # the hosts don't COVER n_segments (fewer visible devices
            # than requested segments) — per-host contiguity would pass
            # while the lane algebra's S = nseg // n_hosts disagrees
            # with the real grouping; never let that stamp host caps
            return False
        for h, segs in enumerate(self.segs_by_host):
            if tuple(segs) != tuple(range(h * S, (h + 1) * S)):
                return False
        return True

    def as_dict(self) -> dict:
        return {
            "n_segments": self.n_segments,
            "n_hosts": self.n_hosts,
            "segs_per_host": self.segs_per_host,
            "uniform_contiguous": self.uniform_contiguous(),
            "forced": self.forced,
            "segments_by_host": {h: list(s)
                                 for h, s in enumerate(self.segs_by_host)},
        }


def host_topology(n_segments: int, device_ids=None) -> HostTopology:
    """Derive the HostTopology for the FIRST n_segments live devices
    (after the optional survivor restriction — the same selection
    ``segment_mesh`` makes, so mesh and topology can never disagree).

    ``CBTPU_FORCE_HOSTS=N`` overrides with N simulated contiguous hosts
    (single-process CPU meshes have one real host; the env knob is how
    tools/ic_bench.py and the tests exercise the DCN-shaped path without
    a cluster). The derivation is cached per (nseg, restriction, force)
    — device lists only change at epoch flips, which change the key."""
    force = os.environ.get("CBTPU_FORCE_HOSTS")
    key = (n_segments,
           tuple(device_ids) if device_ids is not None else None,
           force)
    with _topo_lock:
        hit = _topo_cache.get(key)
    if hit is not None:
        return hit
    if force:
        n_hosts = max(int(force), 1)
        if n_segments % n_hosts != 0:
            raise ValueError(
                f"CBTPU_FORCE_HOSTS={n_hosts} does not divide "
                f"n_segments={n_segments} (simulated hosts are uniform "
                "by construction)")
        S = n_segments // n_hosts
        topo = HostTopology(
            n_segments,
            tuple(tuple(range(h * S, (h + 1) * S))
                  for h in range(n_hosts)),
            forced=True)
    else:
        devices = jax.devices()
        if device_ids is not None:
            _check_device_ids(device_ids, len(devices))
            devices = [devices[i] for i in device_ids]
        hosts: dict[int, list[int]] = {}
        for i, d in enumerate(devices[:n_segments]):
            hosts.setdefault(int(getattr(d, "process_index", 0)),
                             []).append(i)
        topo = HostTopology(
            n_segments,
            tuple(tuple(sorted(hosts[h])) for h in sorted(hosts)))
    with _topo_lock:
        if len(_topo_cache) >= 32:
            _topo_cache.pop(next(iter(_topo_cache)))
        _topo_cache[key] = topo
    return topo


def mesh_topology(n_segments: int) -> dict:
    """Host → segment layout (the gp_segment_configuration view), now a
    rendering of HostTopology. NOTE: reports REAL process grouping plus
    ``this_host``; the forced simulation knob applies here too so the
    observability view matches what the motion layer will do."""
    topo = host_topology(n_segments)
    return {
        "n_segments": n_segments,
        "n_hosts": topo.n_hosts,
        "this_host": jax.process_index(),
        "segments_by_host": {h: list(s)
                             for h, s in enumerate(topo.segs_by_host)},
    }
