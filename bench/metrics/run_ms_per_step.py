"""Milliseconds per step in the host step loop: the program's ``sim.run``
spans over the steps of the sweep's runs, host clock, device time
included."""


def read(ctx):
    span = ctx["spans"].get("sim.run")
    return 1e3 * span["total_s"] / ctx["steps"] if span else None
