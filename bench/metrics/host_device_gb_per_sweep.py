"""Gigabytes per sweep moved between host and device: the program's
``sim.state_put_bytes``, ``sim.state_fetch_bytes`` and
``sim.table_put_bytes`` counters summed, per sweep, 1e9 bytes a GB."""

COUNTERS = ("sim.state_put_bytes", "sim.state_fetch_bytes",
            "sim.table_put_bytes")


def read(ctx):
    found = [ctx["counters"][k] for k in COUNTERS if k in ctx["counters"]]
    return sum(found) / ctx["sweeps"] / 1e9 if found else None
