"""Asynchronous tiled-scan pipeline — prefetch + parallel decode +
device double-buffering.

The tiled executors (exec/tiled.py, exec/tiled_dist.py) stream a table
as fixed-shape tiles; before this module the feed was fully synchronous:
read a micro-partition, decode every column, concatenate, pad, feed —
all on the statement thread, device idle the whole time. JAX's async
dispatch already overlaps *compute* for free; the win left on the table
is moving the HOST work (IO, zstd/zlib/dvarint decode, padding, the
host→device copy) off the critical path — the same shape as a training
input pipeline, and Theseus's data-movement thesis (PAPERS.md) applied
to the scan side instead of the wire.

Pieces:

- ``ScanPipeline``: a bounded prefetch queue (``config.scan_pipeline.
  prefetch_tiles``) fed by ONE background reader thread that runs the
  tile-producing generator. The reader installs the statement's
  lifecycle scope (lifecycle.statement_scope), so cooperative
  cancellation/deadline checks fire inside the worker exactly like on
  the statement thread, and the ``scan_prefetch`` fault seam arms there.
  Producer errors buffer behind already-staged tiles and re-raise on
  the consumer — tile order and content are EXACTLY the synchronous
  feed's, so pipeline on/off is bit-identical by construction.
- double-buffered ``jax.device_put``: when the consumer pops tile k it
  eagerly stages tile k+1 (if already queued) onto the device, so the
  transfer of k+1 overlaps the dispatch of k (single-node path; the
  distributed path stages host-side only — shard_map owns placement).
- a shared decode pool (``decode_workers`` daemon threads) for
  column-parallel micro-partition decode: the codecs release the GIL,
  each worker keeps its own decompression context
  (storage/micropartition.py), and per-column decode seconds feed the
  ``decode_seconds`` histogram so EXPLAIN ANALYZE's tiled trailer can
  attribute stall time to IO vs decode vs compute.

Lifecycle/recovery composition: each ``_run_once`` builds a fresh
pipeline and the tile loops close it in a ``finally`` (close_feed), so
adaptive grow-and-retry restarts drain and reseed the queue, a
checkpoint resume replays from the stream offset (prefetched-but-
unconsumed tiles are simply dropped — progress is consumed tiles, never
staged ones), and a cancelled statement leaves no orphan reader thread
(join with timeout, pinned by tests). Queue memory is charged into the
statement's capacity estimate (queue_charge_bytes → est_pipeline_bytes
→ obs/capacity.record_tiled).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional

from cloudberry_tpu.utils.faultinject import fault_point

_EOS = object()     # producer exhausted
_EMPTY = object()   # nothing queued right now (non-blocking take)


class ScanStats:
    """Per-feed host-side accounting, written by whichever thread runs
    the producing generator (the reader thread when pipelined, the
    statement thread otherwise) and read only after the feed closed —
    no lock by design; the join in close() is the ordering (a timed-out
    join marks the feed leaked and the snapshot is skipped — reading
    would race the still-running writer)."""

    __slots__ = ("decode_s", "read_s", "parts_read", "parts_skipped",
                 "parts_resident", "bytes_decoded", "copy_rows",
                 "view_rows")

    def __init__(self):
        self.decode_s = 0.0      # pure column-decode seconds
        self.read_s = 0.0        # partition read wall (IO + decode)
        self.parts_read = 0
        self.parts_skipped = 0   # resume fast-path: skipped whole files
        self.parts_resident = 0  # served from the HBM buffer pool
        self.bytes_decoded = 0
        self.copy_rows = 0       # rows copied on emit (each at most once)
        self.view_rows = 0       # chunk-exact zero-copy emits

    def snapshot(self) -> dict:
        return {
            "decode_s": round(self.decode_s, 6),
            "read_s": round(self.read_s, 6),
            "parts_read": self.parts_read,
            "parts_skipped": self.parts_skipped,
            "parts_resident": self.parts_resident,
            "bytes_decoded": self.bytes_decoded,
        }


class ScanPipeline:
    """Bounded prefetch queue over a tile generator. Iterating yields
    exactly the generator's items in order; ``close()`` stops the
    reader and joins it. All cross-thread state lives under ``_cond``
    (a leaf: nothing is called while it is held); ``_staged`` is a
    consumer-thread-only slot and never crosses threads."""

    def __init__(self, gen, depth: int = 2, device_stage: bool = False,
                 stats: Optional[ScanStats] = None,
                 span_args: Optional[dict] = None):
        from cloudberry_tpu.lifecycle import current_handle

        self._gen = gen
        # what the consumer's feed-wait and h2d spans carry besides
        # (a tiled build stream's ``phase``)
        self._span_args = span_args or {}
        self.depth = max(int(depth), 1)
        self._device_stage = bool(device_stage)
        self.scan_stats = stats
        self._handle = current_handle()
        self._cond = threading.Condition()
        self._buf: deque = deque()
        self._open = True        # consumer still wants tiles
        self._done = False       # producer finished (or died)
        self._err: Optional[BaseException] = None
        # telemetry (mutations under _cond)
        self.tiles = 0           # tiles staged by the reader
        self.feed_s = 0.0        # producer busy seconds (read+decode+pad)
        self.stall_s = 0.0       # consumer blocked-on-empty-queue seconds
        self.max_depth = 0       # queue high-water mark
        self._staged = None      # consumer-only: device-put next tile
        self._reader_leaked = False  # join timed out in close()
        self._thread = threading.Thread(target=self._reader, daemon=True,
                                        name="cbtpu-scan-reader")
        self._thread.start()

    # ------------------------------------------------------------ producer

    def _reader(self) -> None:
        from cloudberry_tpu.lifecycle import check_cancel, statement_scope

        scope = (statement_scope(self._handle)
                 if self._handle is not None else None)
        if scope is not None:
            scope.__enter__()
        try:
            it = iter(self._gen)
            while True:
                # cancel/deadline seam INSIDE the worker: a cancelled
                # statement stops the prefetch within one tile's work
                check_cancel()
                fault_point("scan_prefetch")
                t0 = time.perf_counter()
                try:
                    item = next(it)
                except StopIteration:
                    break
                if not self._offer(item, time.perf_counter() - t0):
                    break  # consumer closed: stop reading
        except BaseException as e:  # noqa: BLE001 — re-raised on consumer
            with self._cond:
                self._err = e
                self._cond.notify_all()
        finally:
            close = getattr(self._gen, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
            with self._cond:
                self._done = True
                self._cond.notify_all()
            if scope is not None:
                scope.__exit__(None, None, None)

    def _offer(self, item, feed_dt: float) -> bool:
        """Queue one tile, waiting while the bounded buffer is full.
        False when the consumer closed the pipeline."""
        from cloudberry_tpu.lifecycle import check_cancel

        while True:
            with self._cond:
                if not self._open:
                    return False
                if len(self._buf) < self.depth:
                    self._buf.append(item)
                    self.tiles += 1
                    self.feed_s += feed_dt
                    if len(self._buf) > self.max_depth:
                        self.max_depth = len(self._buf)
                    self._cond.notify_all()
                    return True
                self._cond.wait(0.05)
            # outside the lock: the cancel token is its own leaf lock
            check_cancel()

    # ------------------------------------------------------------ consumer

    def __iter__(self) -> "ScanPipeline":
        return self

    def __next__(self):
        if self._staged is not None:
            item = self._staged
            self._staged = None
        else:
            item = self._take(block=True)
            if item is _EOS:
                raise StopIteration
            item = self._stage(item)
        # double-buffer: stage the NEXT tile's device transfer while the
        # caller dispatches this one (non-blocking — never stalls here)
        nxt = self._take(block=False)
        if nxt is not _EOS and nxt is not _EMPTY:
            self._staged = self._stage(nxt)
        return item

    def _take(self, block: bool):
        from cloudberry_tpu.lifecycle import check_cancel
        from cloudberry_tpu.obs import trace as OT

        wait = None  # the ``feed-wait`` stage, open while blocked
        try:
            while True:
                err = None
                with self._cond:
                    if self._buf:
                        item = self._buf.popleft()
                        self._cond.notify_all()
                        return item
                    if not block:
                        # the double-buffer probe must NEVER raise: a
                        # pending producer error belongs to the NEXT
                        # blocking take, after the caller consumed the
                        # tile it already popped
                        return _EOS if (self._done and self._err is None) \
                            else _EMPTY
                    if self._err is not None:
                        # staged tiles drained first: the error surfaces
                        # at the same stream position the synchronous
                        # feed would have raised it
                        err = self._err
                    elif self._done:
                        return _EOS
                    elif wait is not None:
                        self._cond.wait(0.05)
                if err is not None:
                    raise err
                if wait is None:
                    # first look found the queue empty: open the stage
                    # (outside the leaf lock), then look again and wait
                    wait = OT.stage("feed-wait", "launch_seconds",
                                    **self._span_args)
                    wait.__enter__()
                check_cancel()
        finally:
            if wait is not None:
                wait.__exit__(None, None, None)
                # the run report's stall and the histogram: one reading
                self.stall_s += wait.dur

    def _stage(self, item):
        if not self._device_stage:
            return item
        import jax

        from cloudberry_tpu.obs import trace as OT

        tile, n = item
        with OT.stage("h2d", "launch_seconds", bytes=sum(
                int(getattr(v, "nbytes", 0)) for v in tile.values()
                if not isinstance(v, jax.Array)), **self._span_args):
            return ({k: jax.device_put(v) for k, v in tile.items()}, n)

    # ------------------------------------------------------------ teardown

    def close(self) -> None:
        """Stop the reader and release every staged buffer. Idempotent;
        the tile loops call it in a ``finally`` so retries/cancellation
        never leak a reader thread or pin prefetched tiles."""
        with self._cond:
            self._open = False
            self._buf.clear()
            self._cond.notify_all()
        self._thread.join(timeout=10.0)
        # a reader wedged past the join timeout (e.g. a hung partition
        # read) leaks as a daemon thread; record it so stats() never
        # reads ScanStats concurrently with the still-running writer
        self._reader_leaked = self._thread.is_alive()
        self._staged = None

    def stats(self) -> dict:
        with self._cond:
            feed_s = self.feed_s
            rec = {
                "enabled": True,
                "depth": self.depth,
                "tiles_prefetched": self.tiles,
                "max_depth": self.max_depth,
                "feed_s": round(feed_s, 6),
                "stall_s": round(self.stall_s, 6),
            }
        # overlap fraction: the share of producer work hidden behind
        # compute — feed time the consumer did NOT wait for
        if feed_s > 0:
            rec["overlap_frac"] = round(
                max(0.0, 1.0 - min(self.stall_s, feed_s) / feed_s), 4)
        st = self.scan_stats
        if self._reader_leaked:
            rec["reader_leaked"] = True  # snapshot would race the writer
        elif st is not None:
            rec.update(st.snapshot())
        return rec


class PlainFeed:
    """The pipeline-off twin: same close()/scan_stats surface over the
    raw generator, so the tile loops (and the report stamp) treat both
    modes uniformly and the A/B differs only in WHERE the host work
    runs."""

    def __init__(self, gen, stats: Optional[ScanStats] = None):
        self._gen = gen
        self.scan_stats = stats

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._gen)

    def close(self) -> None:
        self._gen.close()

    def stats(self) -> dict:
        rec = {"enabled": False}
        if self.scan_stats is not None:
            rec.update(self.scan_stats.snapshot())
        return rec


def maybe_pipeline(gen, config, device_stage: bool = False,
                   stats: Optional[ScanStats] = None,
                   min_depth: int = 1, span_args: Optional[dict] = None):
    """Wrap a tile generator in the prefetch pipeline when
    ``config.scan_pipeline`` enables it; a PlainFeed otherwise (the
    synchronous path, unchanged semantics). ``min_depth`` lets the
    windowed tile dispatcher (exec/tilepipe.py) deepen the prefetch
    queue to its in-flight window so the feed never becomes the
    bottleneck behind a W-deep device queue; it never turns the
    pipeline ON when the config disabled it. ``span_args``: what the
    feed's spans carry besides."""
    sp = getattr(config, "scan_pipeline", None)
    if sp is not None and sp.enabled and sp.prefetch_tiles >= 1:
        return ScanPipeline(gen, depth=max(sp.prefetch_tiles, min_depth),
                            device_stage=device_stage and sp.device_buffer,
                            stats=stats, span_args=span_args)
    return PlainFeed(gen, stats=stats)


def close_feed(feed) -> None:
    """Deterministic feed teardown for the tile loops' ``finally``:
    works for ScanPipeline, PlainFeed, and bare generators."""
    close = getattr(feed, "close", None)
    if close is not None:
        close()


def stamp_report(report: dict, feed) -> None:
    """Fold the feed's pipeline/decode accounting into the tiled run
    report (read by EXPLAIN ANALYZE's trailer and the bench ladder).
    Call AFTER the loop finished (and the feed closed): the stats are
    stable then."""
    stats_fn = getattr(feed, "stats", None)
    if stats_fn is not None:
        report["pipeline"] = stats_fn()


# ------------------------------------------------------------ decode pool


_pool = None
_pool_workers = 0
_pool_lock = threading.Lock()


def decode_pool(config):
    """The shared column-decode thread pool (daemon workers, lazily
    created, grown to the largest requested size). None when the
    pipeline is off, decode_workers <= 1, or the host exposes a single
    usable core — column-parallel decode cannot win there and the
    extra threads only add GIL contention (measured ~10% regression on
    a 1-core container); callers then decode serially on the reader
    thread, which still overlaps the consumer."""
    global _pool, _pool_workers
    sp = getattr(config, "scan_pipeline", None)
    if sp is None or not sp.enabled or sp.decode_workers <= 1:
        return None
    import os

    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # platforms without affinity
        cores = os.cpu_count() or 1
    if cores < 2:
        return None
    from concurrent.futures import ThreadPoolExecutor

    with _pool_lock:
        if _pool is None or _pool_workers < sp.decode_workers:
            # the superseded pool (if any) is deliberately NOT shut
            # down: a concurrent feed may have captured it, and
            # submit() on a shut-down executor raises. Its idle daemon
            # workers are a bounded, grow-only leak.
            _pool = ThreadPoolExecutor(
                max_workers=sp.decode_workers,
                thread_name_prefix="cbtpu-scan-decode")
            _pool_workers = sp.decode_workers
        return _pool


# --------------------------------------------------------- memory charge


def tile_host_bytes(scan, tile_rows: int, nseg: int = 1) -> int:
    """Host bytes one staged tile pins: every physical column at its
    dtype width plus one bool per validity column, times the padded
    tile shape (× nseg for the distributed (nseg, tile_rows) tiles)."""
    import numpy as np

    width = 0
    for _ in scan.mask_map:
        width += 1
    try:
        for f in scan.fields:
            width += np.dtype(f.type.np_dtype).itemsize
    except Exception:  # noqa: BLE001 — conservative fallback
        width += 8 * max(len(scan.column_map), 1)
    return int(width) * int(tile_rows) * max(int(nseg), 1)


def queue_charge_bytes(scan, tile_rows: int, config,
                       nseg: int = 1) -> int:
    """The capacity-plane charge for the pipeline's staging memory:
    ``prefetch_tiles`` × one tile's working set (obs/capacity.py
    record_tiled adds it to the statement's observed peak)."""
    sp = getattr(config, "scan_pipeline", None)
    if sp is None or not sp.enabled or sp.prefetch_tiles < 1:
        return 0
    return sp.prefetch_tiles * tile_host_bytes(scan, tile_rows, nseg)
