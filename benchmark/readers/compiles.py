"""XLA programs compiled inside the window, as JAX's own compile events
count them (the fetch driver's ``dynamic_slice`` programs too: the run
names them on an earlier line)."""


def read(ctx, args):
    return float(sum(n for n, _s in ctx["compiles"].values()))
