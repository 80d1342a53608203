"""The XLA program (``exec/executor.py Lowerer._join``, the choice
``plan/nodes.py PJoin.direct_lookup``, the proof ``plan/joincap.py
direct_span``): the lookup joins that found their build rows in a
direct-address table in the programs the window launched
(``launch_joins_direct``, counted by ``exec/executor.py
count_join_shapes``) per statement answered. Every send of the cell is
Q18, whose three joins are lookups (the semi-join by ``l_orderkey``'s
aggregate, orders by ``o_orderkey``, customer by ``c_custkey``): 3.0
when all three spans were proven and within the rule, less where a join
kept the sorted search. 0.0 on a program without the counter, as on a
window that answered nothing: the line may not leave a listed metric
out."""


def read(r):
    n = r.answered()
    return r.counter("launch_joins_direct") / n if n else 0.0
