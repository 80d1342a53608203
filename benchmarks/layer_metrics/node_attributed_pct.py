"""The XLA program: the share of the traced sub-window's device busy time
that is charged to a numbered plan node (``harness/nodetime.py``: each
operation's self time, by the program's own map from compiled instruction
to node). What is left: ``answer``, ``checks``, ``tile:merge``, ``input``,
``unscoped`` and the programs no registered program owns (eager helpers,
maps unavailable). Prints the whole table on stderr as ``[nodes]`` lines.
None without a trace; 0.0 where the program under test keeps no map."""


def read(r):
    from benchmarks.harness import nodetime

    table = nodetime.of_reading(r)
    if table is None:
        return None
    nodetime.say(r, table)
    return nodetime.attributed_pct(r)
