"""The XLA program (``exec/dist_executor.py DistLowerer``, which takes
``Lowerer._join``; the choice ``plan/nodes.py PJoin.direct_lookup``):
the lookup joins that found their build rows in a direct-address table
in the programs the window launched (``launch_joins_direct``, counted
by ``exec/executor.py count_join_shapes``) per statement answered. Half
the cell's sends are Q3, whose two lookups (orders by ``o_orderkey`` at
a segment's shard, the broadcast customer by ``c_custkey``) engage it
where the keys' global spans are no larger than the shard's arrays:
1.0; the view joins nothing. 0.0 on a program without the counter (the
parent), as on a window that answered nothing: the line may not leave a
listed metric out."""


def read(r):
    n = r.answered()
    return r.counter("launch_joins_direct") / n if n else 0.0
