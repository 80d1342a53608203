"""The XLA program: device time of the plan nodes of class ``agg``, the
aggregates' and the windows', in the traced sub-window: self time by the
program's own map (``harness/nodetime.py``), mean over the device planes,
per statement answered in it. None without a trace; 0.0 where no such node
ran or the program under test keeps no map."""


def read(r):
    from benchmarks.harness import nodetime

    return nodetime.class_ms_per_stmt(r, "agg")
