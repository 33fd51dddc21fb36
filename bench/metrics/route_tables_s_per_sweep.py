"""Seconds per sweep building the host route tables: the program's
``sim.route_tables`` spans (``repro.sim.tables.build_tables`` inside the
Simulator build), host clock."""


def read(ctx):
    span = ctx["spans"].get("sim.route_tables")
    return span["total_s"] / ctx["sweeps"] if span else None
