"""Share of the step's out-slots that are arcs, %: the program's
``sim.arcs`` counter over N times its ``sim.out_slots_laid`` counter
(both added once per Simulator build), the out-slots the step lays its
state at.  Below 100 where the step pads the out-slot axis past the
graph's degree: the rest of its state traffic is empty slots."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("sim.out_slots_laid") or "sim.arcs" not in c:
        return None
    return 100.0 * c["sim.arcs"] / (ctx["sizes"]["n"]
                                    * c["sim.out_slots_laid"])
