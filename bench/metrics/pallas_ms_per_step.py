"""Device milliseconds per step in the Pallas kernels (custom calls
inside the step program), from the profiler trace."""


def read(ctx):
    t = ctx["trace"]
    if not t.get("step_calls") or not t.get("pallas_s"):
        return None
    return 1e3 * t["pallas_s"] / t["step_calls"]
