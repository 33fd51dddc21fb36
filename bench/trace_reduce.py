"""Reduce one JAX profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``.  On a TPU the trace holds, for each chip, a
plane ``/device:TPU:<i>`` whose line ``XLA Modules`` has one event per
execution of a compiled program (``jit_<function>(<fingerprint>)``) and
whose line ``XLA Ops`` has one event per operation inside it, named by
its HLO instruction.  Pallas kernels are the operations with
``custom_call_target="tpu_custom_call"``.  The host plane ``/host:CPU``
holds the runtime's own events per thread and the benchmark's
``TraceAnnotation`` that marks the measured window.  Every event time is
in nanoseconds from the start of the trace, one clock for all planes.

Numbers over the window (the annotation named ``window_name``):

* ``busy_s`` — the union of the program executions on a chip, averaged
  over the chips that ran any;
* ``step_module``, ``step_calls``, ``step_device_s`` — the program with
  the most device time (the simulator step), its executions and their
  summed device time;
* ``pallas_s`` — device time of the Pallas kernels inside those
  executions;
* ``breakdown`` — the ten operations with the most device time, and the
  idle time of the device summed by what the host was doing: the
  innermost ``repro.obs`` span (placed on the trace clock through the
  window annotation) and the innermost runtime event of the annotating
  thread over the middle of each gap.
"""

from __future__ import annotations

import bisect
import re
from collections import Counter
from pathlib import Path

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")


def find_xplane(directory) -> str:
    found = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return str(found[-1])


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(events, w0, w1):
    return [(max(a, w0), min(b, w1), name) for a, b, name in events
            if b > w0 and a < w1]


def _innermost(events, t):
    """Name of the shortest event covering ``t`` (events: (a, b, name))."""
    best = None
    for a, b, name in events:
        if a <= t < b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return None if best is None else best[2]


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def reduce(path: str, window_name: str = "bench.window", spans=(),
           span_clock=None) -> dict:
    """Device numbers of the window.  ``spans`` are ``repro.obs`` events
    as ``(name, start_perf_ns, dur_ns, depth)``; ``span_clock`` is the
    ``time.perf_counter_ns()`` read on entering the window annotation,
    which places them on the trace clock."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    host = [p for p in pd.planes if p.name == "/host:CPU"]
    win = main = None
    for line in (host[0].lines if host else ()):
        for a, b, name in _events(line):
            if name == window_name:
                win, main = (a, b), line
                break
        if win:
            break
    if win is None:
        raise ValueError(f"no {window_name!r} annotation in {path}")
    w0, w1 = win

    modules, ops = [], []
    busy = []
    for plane in pd.planes:
        if not _DEVICE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        mods = _clip(_events(lines["XLA Modules"]), w0, w1) \
            if "XLA Modules" in lines else []
        if not mods:
            continue
        modules += mods
        ops += _clip(_events(lines["XLA Ops"]), w0, w1) \
            if "XLA Ops" in lines else []
        busy.append(_union((a, b) for a, b, _ in mods))
    if not modules:
        raise ValueError(f"no device program ran inside {window_name!r}")

    per_module = Counter()
    calls = Counter()
    for a, b, name in modules:
        key = _MODULE.match(name).group(1)
        per_module[key] += b - a
        calls[key] += 1
    step = per_module.most_common(1)[0][0]
    step_iv = sorted((a, b) for a, b, name in modules
                     if _MODULE.match(name).group(1) == step)
    starts = [a for a, _ in step_iv]

    def in_step(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < step_iv[i][1]

    pallas = 0
    per_op = Counter()
    for a, b, name in ops:
        m = _TARGET.search(name)
        if m and m.group(1) == "tpu_custom_call" and in_step(a):
            pallas += b - a
        per_op[name.split(" ")[0].lstrip("%")] += b - a

    busy_ns = sum(sum(b - a for a, b in u) for u in busy) / len(busy)

    # idle gaps of the first chip that ran, named by the host's activity
    gaps = []
    t = w0
    for a, b in busy[0]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    span_ev = []
    if span_clock is not None:
        off = w0 - span_clock
        span_ev = [(s + off, s + off + d, n) for n, s, d, _depth in spans]
    host_ev = [e for e in _events(main) if e[2] != window_name]
    idle = Counter()
    for a, b in gaps:
        mid = 0.5 * (a + b)
        parts = [_innermost(span_ev, mid), _innermost(host_ev, mid)]
        idle[" / ".join(p for p in parts if p) or "host"] += b - a

    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "chips": len(busy),
        "step_module": step,
        "step_calls": calls[step] / len(busy),
        "step_device_s": per_module[step] / 1e9 / len(busy),
        "pallas_s": pallas / 1e9 / len(busy),
        "breakdown": {
            "device_ops": [[n, d / 1e9] for n, d in per_op.most_common(10)],
            "idle_gaps": [[n, d / 1e9] for n, d in idle.most_common(10)],
        },
    }
