"""Wire + front end: what a statement costs outside the server's request
span: the mean client send-to-answer time of the window's answered sends
minus ``request_ms``. The socket both ways, the client threads, and the
interpreter lock held off the server (clients and server share one
process)."""


def read(r):
    ok = [s.seconds for s in r.sends if s.error is None]
    n = r.answered()
    if not ok or not n:
        return 0.0
    return (sum(ok) / len(ok) - r.hist("request_seconds")[1] / n) * 1e3
