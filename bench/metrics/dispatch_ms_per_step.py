"""Milliseconds per step the host spends handing the step program to the
device: the program's ``sim.step_dispatch`` spans over the steps of the
sweep's runs, host clock."""


def read(ctx):
    span = ctx["spans"].get("sim.step_dispatch")
    return 1e3 * span["total_s"] / ctx["steps"] if span else None
