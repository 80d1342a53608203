"""The planner and the XLA program (``plan/fdep.py carried_keys``,
``exec/executor.py Lowerer.agg``): the grouping keys of the programs the
window launched that were carried rather than sorted, each taken at its
group's first row because the sorted keys determine it by proof
(``launch_agg_keys_carried``, counted by ``exec/executor.py
count_agg_shapes`` beside ``launch_agg_rows_in``) per statement
answered. Q18's five-key GROUP BY sorts ``o_orderkey`` and carries
``c_name``, ``c_custkey``, ``o_orderdate`` and ``o_totalprice``: 4.0 in a
cell that sends Q18 alone. 0.0 on a program without the counter (an
older one), as on a window that answered nothing: the line may not leave a
listed metric out."""


def read(r):
    n = r.answered()
    return r.counter("launch_agg_keys_carried") / n if n else 0.0
