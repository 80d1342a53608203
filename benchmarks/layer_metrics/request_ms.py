"""Wire + front end: ``request_seconds`` (a line's arrival at the server to
its answer's last byte sent) summed over the window, per statement
answered. 0.0 where the program has no such histogram."""


def read(r):
    n = r.answered()
    return (r.hist("request_seconds")[1]) / n * 1e3 if n else 0.0
