"""The XLA program (``exec/executor.py Lowerer.agg``, sized by
``plan/joincap.py``): the capacity the window's grouped aggregates emit
at, as a share of the capacity their rows arrive at
(``launch_agg_capacity`` / ``launch_agg_rows_in``, both summed over the
grouped aggregates of every program launched, counted by
``exec/executor.py count_agg_shapes``). Whatever runs above an
aggregate runs at its capacity: 100 where every aggregate emits at its
input's capacity (Q13 before the proven ceilings: 1,671,168 rows into
both of its aggregates and its sort), 16.6 where Q13's first aggregate
is held to customer's rows ((151,552 + 151,552) / (1,671,168 +
151,552)). 0.0 on a program without the counters (the parent), as on a
window that launched no grouped aggregate: the line may not leave a
listed metric out."""


def read(r):
    rows_in = r.counter("launch_agg_rows_in")
    return 100.0 * r.counter("launch_agg_capacity") / rows_in \
        if rows_in else 0.0
