"""Peak device memory in use after the window (the runtime's
``memory_stats()["peak_bytes_in_use"]``), GiB."""


def read(ctx):
    return ctx["peak_bytes"] / 2**30 if ctx["peak_bytes"] else None
