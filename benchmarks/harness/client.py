"""The closed-loop clients and the arithmetic of the end-to-end metrics.

Copied in shape from ``tools/serve_bench.py``'s worker loop and ``_pct``:
one OS thread per stream, each with a TCP connection of its own to the
server, sending its next statement when the last one is answered. Every
send is kept: when it went, when its answer came, what it said.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field


@dataclass
class Send:
    stream: int
    stmt: str
    params: dict
    t_send: float
    t_done: float = 0.0
    answer: dict | None = None      # the wire response (columns, rows)
    error: str | None = None

    @property
    def seconds(self) -> float:
        return self.t_done - self.t_send


def percentile(sorted_values: list, p: float) -> float:
    """Nearest-rank percentile of an ascending list (serve_bench's)."""
    if not sorted_values:
        raise ValueError("no samples")
    return sorted_values[min(len(sorted_values) - 1,
                             int(p * len(sorted_values)))]


def end_to_end(sends: list, t_open: float, t_close: float) -> dict:
    """The client's side of the window [t_open, t_close]: every send of
    the window counts, the one in flight at the close with the wait it
    really had; the rate is of the answers that came inside the window
    over the window's whole length."""
    ok = [s for s in sends if s.error is None]
    lats = sorted(s.seconds for s in ok)
    inside = sum(1 for s in ok if s.t_done <= t_close)
    return {"stmt_per_s": inside / (t_close - t_open),
            "lat_p50_ms": percentile(lats, 0.50) * 1e3,
            "lat_p95_ms": percentile(lats, 0.95) * 1e3}


@dataclass
class Stream:
    """One closed-loop client. ``run`` is the warm-up's and the window's
    one call: the same connection, the same ``Client.sql``."""
    index: int
    client: object                          # serve.Client, connected
    render: object                          # (stmt, params) -> sql text
    sends: list = field(default_factory=list)

    def one(self, stmt: str, params: dict) -> Send:
        s = Send(self.index, stmt, params, time.perf_counter())
        try:
            s.answer = self.client.sql(self.render(stmt, params))
        except Exception as e:  # the send failed; the run goes on
            s.error = f"{type(e).__name__}: {e}"
        s.t_done = time.perf_counter()
        return s

    def run_until(self, source, t_close: float) -> None:
        for stmt, params in source:
            if time.perf_counter() >= t_close:
                return
            self.sends.append(self.one(stmt, params))


def run_streams(streams: list, sources: list, seconds: float) -> tuple:
    """All streams at once for ``seconds``; returns (t_open, t_close).
    Each stream's list of sends is emptied first."""
    gate = threading.Barrier(len(streams) + 1)
    close = [0.0]

    def body(stream, source):
        stream.sends = []
        gate.wait()
        stream.run_until(source, close[0])

    threads = [threading.Thread(target=body, args=(st, src), daemon=True)
               for st, src in zip(streams, sources)]
    for t in threads:
        t.start()
    t_open = time.perf_counter()
    close[0] = t_open + seconds
    gate.wait()
    return t_open, close[0], threads
