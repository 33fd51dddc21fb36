"""Seconds per sweep copying each run's final state to the host (and
dropping the previous run's): the program's ``sim.state_fetch`` spans,
host clock."""


def read(ctx):
    span = ctx["spans"].get("sim.state_fetch")
    return span["total_s"] / ctx["sweeps"] if span else None
