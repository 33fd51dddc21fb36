"""Device milliseconds per step of the jitted step program, from the
profiler trace (``bench/trace_reduce.py``)."""


def read(ctx):
    t = ctx["trace"]
    if not t.get("step_calls"):
        return None
    return 1e3 * t["step_device_s"] / t["step_calls"]
