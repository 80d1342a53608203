"""Compile: programs handed to the compiler inside the window, the larger
of the engine's ``compiles`` counter and JAX's own compile events (the
tiled path does not move the first). 0 is the expected reading."""


def read(r):
    jax_side = (r.after["jax_compiles"]["programs"]
                - r.before["jax_compiles"]["programs"])
    return max(r.counter("compiles"), jax_side)
