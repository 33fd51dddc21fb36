"""Seconds per sweep placing the step's tables on the device: the
program's ``sim.table_put`` spans, host clock, synced to the transfer."""


def read(ctx):
    span = ctx["spans"].get("sim.table_put")
    return span["total_s"] / ctx["sweeps"] if span else None
