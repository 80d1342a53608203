"""The XLA program (``exec/executor.py Lowerer._join``, stamped by
``plan/joincap.py``): the lookup joins that ran at a capacity of their
own in the programs the window launched (``launch_joins_compacted``,
counted by ``exec/executor.py count_join_shapes`` beside
``launch_joins_lookup``) per statement answered. Half the cell's sends
are Q3 (two: lineitem's probes match few orders, orders' few customers:
the matched rows are compacted before the payload gathers), half Q12
(one: its filter keeps 0.6 % of lineitem, compacted before the search):
1.5 when the planner's estimates called all three sparse, less where a
join ran at its probe scan's capacity (PERF.md section 6, PR 33).
0.0 on a program without the counter (the parent), as on a window that
answered nothing: the line may not leave a listed metric out."""


def read(r):
    n = r.answered()
    return r.counter("launch_joins_compacted") / n if n else 0.0
