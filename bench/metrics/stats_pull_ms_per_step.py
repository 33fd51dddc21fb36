"""Milliseconds per step the host waits for a step's six statistics: the
program's ``sim.stats_pull`` spans over the steps of the sweep's runs,
host clock (the wait for the step on the device included)."""


def read(ctx):
    span = ctx["spans"].get("sim.stats_pull")
    return 1e3 * span["total_s"] / ctx["steps"] if span else None
