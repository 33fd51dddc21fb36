"""Seconds per sweep building each run's zero state and placing it on
the device: the program's ``sim.state_put`` spans, host clock, synced to
the transfer."""


def read(ctx):
    span = ctx["spans"].get("sim.state_put")
    return span["total_s"] / ctx["sweeps"] if span else None
