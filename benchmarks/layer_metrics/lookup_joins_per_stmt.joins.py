"""Session (``plan/``): the sorted-build lookup joins in the programs the
window launched (``launch_joins_lookup``, counted by ``exec/executor.py
count_join_shapes`` from the shapes fixed when a plan is lowered) per
statement answered. Half the cell's sends are Q3 (two lookups: orders by
``o_orderkey``, customer by ``c_custkey``), half Q12 (one: orders by
``o_orderkey``): 1.5 when the planner was told which builds are unique,
less when a lookup fell back to a pair expansion (PERF.md section 6, PR
31).
0.0 on a program without the counter, as on a window that answered
nothing: the line may not leave a listed metric out. (A file of its own,
because ``lookup_joins_per_stmt.4seg.py`` is one and so there is no base
file to fall back on.)"""


def read(r):
    n = r.answered()
    return r.counter("launch_joins_lookup") / n if n else 0.0
