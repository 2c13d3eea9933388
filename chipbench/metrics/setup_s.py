"""Seconds from process start (before JAX is imported) to the window:
weights, engine, compile or cache reads, and the warm-up traffic."""


def read(rec):
    return rec.setup_s
