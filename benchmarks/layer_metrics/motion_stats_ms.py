"""Distributed launch: ``launch_seconds.motion_stats`` (the host reads the
program's motion statistics and checks, a blocking read a leaf, and folds
them into the feedback store) per statement answered. 0.0 on a program
without the stage, as on a window that answered nothing: the line may not
leave a listed metric out."""


def read(r):
    n = r.answered()
    return r.hist("launch_seconds.motion_stats")[1] / n * 1e3 if n else 0.0
