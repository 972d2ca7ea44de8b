"""Programs built inside the window (backend compiles, a persistent-cache
hit included), from JAX's compile events."""


def read(rec):
    return rec["compiles"]
