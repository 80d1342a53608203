"""Session: the share of statements answered that bound their literals
through a literal template (``template_binds``, counted by
``sched/paramplan.py template_bind``), without parse or plan. 0.0 on a
program without the counter, as on a window that answered nothing: the
line may not leave a listed metric out."""


def read(r):
    n = r.answered()
    return 100.0 * r.counter("template_binds") / n if n else 0.0
