"""The XLA program (``exec/executor.py Lowerer._join``, the expansion
branch: ``kernels.join_expand_sorted`` into a pair buffer of the join's
``out_capacity``, with an overflow check that the session answers by
growing the buffer): the joins lowered as pair expansions in the
programs the window launched (``launch_joins_expand``, counted by
``exec/executor.py count_join_shapes``) per statement answered. Which
shape a join takes is the planner's choice (``PJoin.expands``: a build
whose key is not unique, or a residual predicate). Q13's outer join
builds on ``o_custkey``, which is no key of orders: 1.0 in a cell that
sends Q13 alone; 0.0 where every join is a sorted-build lookup, on a
program without the counter, and on a window that answered nothing: the
line may not leave a listed metric out."""


def read(r):
    n = r.answered()
    return r.counter("launch_joins_expand") / n if n else 0.0
