"""Session (``plan/``): the sorted-build lookup joins in the programs the
window launched (``launch_joins_lookup``, counted by ``exec/executor.py
count_join_shapes`` from the shapes fixed when a plan is lowered) per
statement answered. Half the cell's sends are Q3, whose two joins have
unique builds (``o_orderkey``, ``c_custkey``): 1.0 when the planner was
told so, 0.0 when it was not and expanded both. 0.0 on a program without
the counter too, as on a window that answered nothing: the line may not
leave a listed metric out."""


def read(r):
    n = r.answered()
    return r.counter("launch_joins_lookup") / n if n else 0.0
