"""Unified observability plane: statement trace spans (obs/trace.py),
the engine-wide metrics registry (obs/metrics.py), per-skeleton
statement aggregates (obs/statements.py), and — the capacity &
forensics layer (ISSUE 12) — per-statement device-memory accounting +
engine memory gauges (obs/capacity.py), live statement progress
(obs/progress.py), and the slow-statement flight recorder
(obs/flightrec.py). The shared StatementLog (exec/instrument.py) owns
one instance of each, so a server's backends write one telemetry plane;
``meta "metrics"/"statements"/"trace"/"progress"/"flight"`` ship
snapshots over the wire."""

from cloudberry_tpu.obs.metrics import (CounterView,  # noqa: F401
                                        MetricsRegistry)
from cloudberry_tpu.obs.progress import (Progress,  # noqa: F401
                                         current_progress)
from cloudberry_tpu.obs.statements import StatementStats  # noqa: F401
from cloudberry_tpu.obs.trace import (Request, Trace,  # noqa: F401
                                      chrome_trace, current_trace, stage,
                                      stage_since)
