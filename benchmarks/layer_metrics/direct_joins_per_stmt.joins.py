"""The XLA program (``exec/executor.py Lowerer._join``, the choice
``plan/nodes.py PJoin.direct_lookup``, the proof ``plan/joincap.py
direct_span``): the lookup joins that found their build rows in a
direct-address table, one gather and no binary search, in the programs
the window launched (``launch_joins_direct``, counted by
``exec/executor.py count_join_shapes`` beside ``launch_joins_lookup``)
per statement answered. Half the cell's sends are Q3 (two: orders by
``o_orderkey``, customer by ``c_custkey``), half Q12 (one: orders by
``o_orderkey``): 1.5 when all three keys' spans were proven and within
the rule, less where a join kept the sorted search (PERF.md section 6,
PR 37). 0.0 on a program without the counter (the parent), as on a
window that answered nothing: the line may not leave a listed metric
out."""


def read(r):
    n = r.answered()
    return r.counter("launch_joins_direct") / n if n else 0.0
