"""Seconds per sweep in the Simulator build: the program's
``sim.build_tables`` span (route tables, step build, device placement of
the tables), host clock."""


def read(ctx):
    span = ctx["spans"].get("sim.build_tables")
    return span["total_s"] / ctx["sweeps"] if span else None
