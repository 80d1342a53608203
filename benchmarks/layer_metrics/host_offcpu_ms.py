"""Wire + front end: mean ``host_offcpu_seconds`` of the requests that
sampled it (one in eight reads its thread's CPU clock: a system call):
wall minus thread-CPU time of the request's host-only stages (wire-in,
parse, plan, admit, bind, inputs, fetch, render, wire-out, wire-flush).
In one process with the clients this is, to a first approximation, the
wait for the interpreter lock (fetch's wait for the device-to-host copy
is in it too). 0.0 where the program has no such histogram."""


def read(r):
    n, seconds = r.hist("host_offcpu_seconds")
    return seconds / n * 1e3 if n else 0.0
