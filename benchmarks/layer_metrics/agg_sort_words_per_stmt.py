"""The XLA program (``exec/kernels.py group_layout``, the packing proven
by ``plan/cost.py annotate_pack_bits``): the 32-bit words the grouping
sorts of the programs the window launched compare, summed over their
grouped aggregates (``launch_agg_sort_words``, counted by
``exec/executor.py count_agg_shapes`` beside ``launch_agg_rows_in``: a
32-bit pack is one word, a 64-bit pack two, unpacked keys their own; the
row's position, which every sort carries last, is not counted) per
statement answered. The TPU compiler's time for a sort grows with these
words. Q18: ``l_orderkey``'s aggregate one word, the five-key aggregate
one where ``o_orderkey`` alone is sorted: 2.0. 0.0 on a program without
the counter (an older one), as on a window that answered nothing: the
line may not leave a listed metric out."""


def read(r):
    n = r.answered()
    return r.counter("launch_agg_sort_words") / n if n else 0.0
