"""What a per-layer reader is handed: the counters and timers on both
sides of the window, the window's sends, and the reduced trace of the
sub-window. A reader takes what its metric needs and returns a number,
or None where there was nothing to read (the metric is then left out of
the line)."""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Reading:
    before: dict                 # system.snapshot at the window's open
    after: dict                  # ... and at its close
    sends: list                  # the window's sends (client.Send)
    t_open: float
    t_close: float
    cell: object                 # harness.cell.Cell
    rows: dict                   # table -> rows loaded
    device: dict                 # platform, kind, count
    peaks: dict                  # peaks.json
    trace: dict = field(default_factory=dict)   # trace.reduce_file, or {}
    sub: tuple | None = None     # (t_start, t_stop) of the sub-window
    rehearsal: bool = False      # no chip: peaks come from "rehearsal"

    def counter(self, name: str) -> int:
        return (self.after["counters"].get(name, 0)
                - self.before["counters"].get(name, 0))

    def hist(self, name: str) -> tuple:
        """(samples, summed seconds) the window added to a histogram."""
        n1, s1 = self.after["hists"].get(name, (0, 0.0))
        n0, s0 = self.before["hists"].get(name, (0, 0.0))
        return n1 - n0, s1 - s0

    def answered(self) -> int:
        """Statements the server answered inside the window, by its own
        count (the clients' last sends may still be open at the close)."""
        return self.hist("statement_seconds")[0]

    def peak(self, key: str) -> float:
        kind = "rehearsal" if self.rehearsal else self.device["kind"]
        if kind not in self.peaks:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           "peaks.json: add it with its source")
        return float(self.peaks[kind][key])

    def sub_statements(self) -> list:
        """[(statement, share)] of the sends that overlap the traced
        sub-window, each with the share of its send-to-answer interval
        that lies inside it."""
        if self.sub is None:
            return []
        lo, hi = self.sub
        out = []
        for s in self.sends:
            if s.error is None and s.t_done > lo and s.t_send < hi \
                    and s.t_done > s.t_send:
                inside = min(s.t_done, hi) - max(s.t_send, lo)
                out.append((s.stmt, inside / (s.t_done - s.t_send)))
        return out
