"""Probes per sweep: the ``sim.probes[grid|bracket|bisect]`` counters of
the program's obs session over the traced window, per sweep."""


def read(ctx):
    probes = sum(v for k, v in ctx["counters"].items()
                 if k.startswith("sim.probes["))
    return probes / ctx["sweeps"] if probes else None
