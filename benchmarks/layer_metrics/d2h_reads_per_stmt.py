"""Launch, one-shot path: blocking device-to-host reads
(``launch_d2h_reads``, counted by ``run_executable``) per statement
answered. 0.0 on a program without the counter, as on a window that
answered nothing: the line may not leave a listed metric out."""


def read(r):
    n = r.answered()
    return r.counter("launch_d2h_reads") / n if n else 0.0
