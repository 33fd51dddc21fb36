"""Share of the HBM roofline of one step: the fluid state read once and
written once (``bench/state_bytes.py``, from the deployment's sizes) over
the step program's device time times the peak HBM bandwidth."""

import state_bytes


def read(ctx):
    t, z = ctx["trace"], ctx["sizes"]
    if not t.get("step_calls") or not t["step_device_s"] > 0:
        return None
    need = state_bytes.step_bytes(z["n"], z["k"], z["m"], z["c"],
                                  z["itemsize"])
    per_step = t["step_device_s"] / t["step_calls"]
    return 100.0 * need / (per_step * ctx["peaks"]["hbm_bytes_per_s"])
