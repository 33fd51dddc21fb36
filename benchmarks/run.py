"""Benchmark harness: one entry per paper table/figure + the traffic and
adversarial-routing sweeps + the fabric planner + the roofline summary.
Prints ``name,us_per_call,derived`` CSV rows where ``derived`` is the
headline validation number for that artifact (max relative error vs. the
paper, or the key reproduced quantity).

``--json PATH`` additionally records per-entry wall time and the numeric
``max_rel_err`` (where the artifact has one) so future changes have a perf
trajectory to regress against, and the run exits nonzero when any entry's
``max_rel_err`` exceeds ``--err-budget`` (default 0.25) — a reproduction
or routing-invariant regression fails CI loudly instead of only being
recorded:

    python -m benchmarks.run --json BENCH_topology.json --only tables
    python -m benchmarks.run --json BENCH_3.json --only routing

Sections degrade gracefully: a crashed section is reported (and recorded
under ``errors`` in the JSON payload) while the remaining sections still
run and the partial artifact is still written — the run then exits
nonzero, so CI fails without losing the data that DID compute.

The arc-load engine behind the tables is selected by REPRO_PERF (see
repro.perf); e.g. ``REPRO_PERF=util_engine=naive`` times the reference
implementation for comparison.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import time
import traceback

# bump when the JSON payload layout changes; benchmarks/compare.py reads it
SCHEMA_VERSION = 2


def _git_rev() -> str | None:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        rev = out.stdout.strip()
        return rev if out.returncode == 0 and rev else None
    except Exception:
        return None


def _run(records, name, fn, derive, err_of=None):
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0
    derived = derive(out)
    print(f"{name},{dt * 1e6:.1f},{derived}", flush=True)
    rec = {"name": name, "seconds": round(dt, 6), "derived": derived}
    if err_of is not None:
        rec["max_rel_err"] = float(err_of(out))
    records.append(rec)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write per-entry wall time + max_rel_err as JSON")
    ap.add_argument("--only",
                    choices=["tables", "figures", "traffic", "routing",
                             "placement", "sim", "faults", "kernels",
                             "hlo", "all"],
                    default="all",
                    help="restrict to the paper tables, figures, the "
                         "traffic-pattern saturation sweep, the "
                         "adversarial routing-model table, the "
                         "placement strategy/fragmentation table, the "
                         "simulator parity table (BENCH_5), the "
                         "fault degradation curves (BENCH_6), or the "
                         "fused step kernel rows (BENCH_7); 'hlo' (the "
                         "compile-and-rank op breakdown) runs only when "
                         "named explicitly — it is NOT part of 'all'")
    ap.add_argument("--err-budget", type=float, default=0.25, metavar="E",
                    help="fail (exit 1) when any entry's max_rel_err exceeds "
                         "E instead of only recording it (negative: record "
                         "only)")
    ap.add_argument("--obs", choices=["none", "metrics", "trace"],
                    default="trace",
                    help="per-section repro.obs capture embedded under "
                         "'obs' in the JSON payload (default: trace with "
                         "per-step series capture OFF, so span/counter "
                         "recording stays out of the hot loops)")
    ap.add_argument("--stream", metavar="PATH", default=None,
                    help="append live JSONL telemetry (section boundaries "
                         "+ in-section progress/probe events) to PATH "
                         "while the run is going; tail -f it to watch a "
                         "long benchmark instead of waiting for the JSON")
    args = ap.parse_args(argv)
    from repro.jaxenv import enable_compile_cache
    enable_compile_cache()

    records: list[dict] = []
    errors: list[dict] = []
    obs_by_section: dict[str, dict] = {}
    streamer = None
    if args.stream:
        from repro.obs import ObsStreamer
        streamer = ObsStreamer(args.stream)
    print("name,us_per_call,derived")

    def section(name, body):
        """Run one bench section; a crash is reported and recorded but
        never takes the other sections (or the JSON artifact) with it.
        Each section gets its own obs session so the embedded span/metric
        snapshot attributes the work to the section that did it.  The
        shared ``--stream`` file (when open) receives the section
        boundaries directly and rides into each session so in-section
        emitters (sweep probes, Progress) stream through it too."""
        t0 = time.perf_counter()
        if streamer is not None:
            streamer.emit("section", name=name, state="start")
        ok = True
        try:
            if args.obs == "none":
                body()
                return
            from repro import obs
            with obs.session(mode=args.obs, series=False,
                             stream=streamer) as sess:
                try:
                    body()
                finally:
                    snap = sess.snapshot()
                    if snap is not None:
                        obs_by_section[name] = snap
        except Exception as e:
            ok = False
            print(f"# SECTION FAILED [{name}]: {type(e).__name__}: {e}",
                  file=sys.stderr)
            errors.append({"section": name,
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()})
        finally:
            if streamer is not None:
                streamer.emit("section", name=name, state="end", ok=ok,
                              seconds=round(time.perf_counter() - t0, 3))

    def run_tables():
        from . import paper_tables as tabs
        for name, fn in tabs.TABLES.items():
            _run(records, name, fn, lambda o: f"max_err={o[1]:.4f}",
                 err_of=lambda o: o[1])

    def run_traffic():
        from . import traffic as traf
        for case_name, g in traf.traffic_cases():
            out = _run(records, f"traffic[{case_name}]",
                       lambda g=g: traf.traffic_one(g),
                       lambda o: (f"min_theta={o[1]['minimal']['min_theta']:.4f}"
                                  f"@{o[1]['minimal']['worst_pattern']}"
                                  f" valiant={o[1]['valiant']['min_theta']:.4f}"))
            records[-1]["patterns"] = out[0]
            records[-1]["summary"] = out[1]

    def run_routing():
        from . import routing_bench as rb
        for case_name, g in rb.routing_cases():
            out = _run(records, f"routing[{case_name}]",
                       lambda g=g: rb.routing_one(g),
                       lambda o: (f"ugal_worst={o[1]['ugal']['min_theta']:.4f}"
                                  f"@{o[1]['ugal']['worst_pattern']}"
                                  f" min={o[1]['minimal']['min_theta']:.4f}"
                                  f" val={o[1]['valiant']['min_theta']:.4f}"),
                       err_of=lambda o: o[2])
            records[-1]["rows"] = out[0]
            records[-1]["worst"] = out[1]

    def run_sim():
        from . import sim_bench as sb
        for case_name, case in sb.sim_cases():
            out = _run(records, f"sim[{case_name}]",
                       lambda case=case: sb.sim_one(case),
                       lambda o: (f"theta={o[0]['theta_sim']:.4f}"
                                  f" analytic={o[0]['theta_analytic']:.4f}"
                                  f" kind={o[0]['kind']}"),
                       err_of=lambda o: o[1])
            records[-1]["row"] = out[0]

    def run_placement():
        from . import placement_bench as pb
        for case_name, g, mesh, axes, d0, exp in pb.placement_cases():
            out = _run(records, f"placement[{case_name}]",
                       lambda g=g, mesh=mesh, axes=axes, d0=d0, exp=exp:
                           pb.placement_one(g, mesh, axes, d0, exp),
                       lambda o: (f"ep_best={o[1]['ep_heavy']['best']}"
                                  f"@{o[1]['ep_heavy']['best_theta']:.4f}"
                                  f" lin={o[1]['ep_heavy']['linear_theta']:.4f}"
                                  f" frag={o[1]['fragmentation']['best']}"),
                       err_of=lambda o: o[2])
            records[-1]["rows"] = out[0]
            records[-1]["summary"] = out[1]

    def run_faults():
        from . import fault_bench as fb
        for case_name, g in fb.fault_cases():
            for routing in fb.MODELS:
                out = _run(records, f"faults[{case_name}:{routing}]",
                           lambda g=g, routing=routing:
                               fb.fault_one(g, routing),
                           lambda o: (f"theta_k={','.join(f'{v:.3f}' for v in o[0]['mean_theta'])}"
                                      f" worst_k5={o[0]['worst_theta'][-1]:.3f}"),
                           err_of=lambda o: o[1])
                records[-1]["row"] = out[0]
        out = _run(records, "faults[sim_parity:torus2d_8x16]",
                   fb.sim_parity_row,
                   lambda o: (f"static={o[0]['theta_static']:.4f}"
                              f" dynamic={o[0]['theta_dynamic']:.4f}"
                              f" gap={o[0]['knee_gap']:.4f}"),
                   err_of=lambda o: o[1])
        records[-1]["row"] = out[0]

    def run_kernels():
        from . import kernel_bench as kb
        out = _run(records, "kernels[pn16:step_timing]", kb.step_timing,
                   lambda o: (f"numpy={o[0]['ms_per_step']['numpy']:.1f}ms"
                              f" jax={o[0]['ms_per_step']['jax']:.1f}ms"
                              f" pallas={o[0]['ms_per_step']['pallas']:.1f}ms"),
                   err_of=lambda o: o[1])
        records[-1]["row"] = out[0]
        out = _run(records, "kernels[pn16:sweep]", kb.pn16_sweep,
                   lambda o: (f"theta={o[0]['theta_sim']:.4f}"
                              f" analytic={o[0]['theta_analytic']:.4f}"
                              f" speedup={o[0]['speedup']:.1f}x"),
                   err_of=lambda o: o[1])
        records[-1]["row"] = out[0]
        out = _run(records, "kernels[pn16:ugal_compacted]",
                   kb.pn16_ugal_compacted,
                   lambda o: (f"knee={o[0]['theta_sim']:.4f}"
                              f" analytic={o[0]['theta_analytic']:.4f}"
                              f" cols={o[0]['compacted_dests']}/{o[0]['dense_dests']}"
                              f" speedup={o[0]['speedup']:.1f}x"),
                   err_of=lambda o: o[1])
        records[-1]["row"] = out[0]
        out = _run(records, "kernels[pn27:ugal]", kb.pn27_ugal,
                   lambda o: (f"theta={o[0]['theta_sim']:.4f}"
                              f" analytic={o[0]['theta_analytic']:.4f}"
                              f" cells={o[0]['dense_cells']}"
                              f" dests={o[0]['compacted_dests']}"),
                   err_of=lambda o: o[1])
        records[-1]["row"] = out[0]
        out = _run(records, "kernels[pn27:sweep]", kb.pn27_sweep,
                   lambda o: (f"theta={o[0]['theta_sim']:.4f}"
                              f" analytic={o[0]['theta_analytic']:.4f}"
                              f" cells={o[0]['dense_cells']}"
                              f" backend={o[0]['backend']}"),
                   err_of=lambda o: o[1])
        records[-1]["row"] = out[0]

    def run_figures():
        from . import paper_figures as figs
        _run(records, "fig5_mms_vs_moore", figs.fig5,
             lambda o: f"tail_vs_8/9_err={o[1]:.4f}", err_of=lambda o: o[1])
        _run(records, "fig6_mms_utilization", figs.fig6,
             lambda o: f"tail_vs_8/9_err={o[1]:.4f}", err_of=lambda o: o[1])
        _run(records, "fig7_cost_vs_bound", figs.fig7,
             lambda o: f"bound_violation={o[1]:.4f}", err_of=lambda o: o[1])
        _run(records, "fig8_scalability", figs.fig8, lambda o: f"rows={len(o[0])}")
        _run(records, "fig9_pn_vs_slimfly", figs.fig9,
             lambda o: f"demi_pn_worse_than_sf_cases={o[1]:.0f}")

    def run_hlo():
        # compile-and-rank op breakdown for the smallest arch; explicit
        # --only hlo opt-in (a full XLA compile is far slower than any
        # paper table, so it never rides under "all")
        from . import hlo_breakdown as hb
        out = _run(records, "hlo[smollm-135m:train_4k]",
                   lambda: hb.breakdown("smollm-135m", "train_4k", top=10),
                   lambda o: (f"flops={o['flops_per_device']:.3e}"
                              f" kinds={len(o['by_kind'])}"
                              f" collectives={len(o['collectives'])}"))
        records[-1]["row"] = out

    sections = [("tables", run_tables), ("traffic", run_traffic),
                ("routing", run_routing), ("sim", run_sim),
                ("placement", run_placement), ("faults", run_faults),
                ("kernels", run_kernels), ("figures", run_figures),
                ("hlo", run_hlo)]
    for name, body in sections:
        if args.only == name or (args.only == "all" and name != "hlo"):
            section(name, body)

    if args.only == "all":
        # fabric planner on a real dry-run profile when available
        try:
            from repro.fabric import StepProfile, plan

            from .roofline import load_records
            recs = [r for r in load_records() if r.get("status") == "ok"
                    and r.get("shape") == "train_4k"]
            if recs:
                rec = max(recs, key=lambda r: r["collective_bytes_per_device"]
                          .get("total", 0))
                prof = StepProfile.from_dryrun(rec)

                def _best(rows):
                    # paper's Section-5 rule: cheapest fabric within 5% of the
                    # best step time (all candidates are full-bisection sized)
                    t0 = rows[0]["step_comm_ms"]
                    near = [r for r in rows if r["step_comm_ms"] <= 1.05 * t0]
                    c = min(near, key=lambda r: r["usd_per_node"])
                    return f"best={c['fabric']}@{c['usd_per_node']}$"
                _run(records, f"fabric_planner[{rec['arch']}]",
                     lambda: plan(prof, min_terminals=10000), _best)
        except Exception as e:  # planner needs dry-run artifacts
            print(f"fabric_planner,0,unavailable({type(e).__name__})")

        # roofline summary over whatever cells have been dry-run
        try:
            from .roofline import roofline_table
            rows, skipped, errors = roofline_table()
            n_dom = {}
            for r in rows:
                n_dom[r["dominant"]] = n_dom.get(r["dominant"], 0) + 1
            print(f"roofline_summary,0,cells={len(rows)} skipped={len(skipped)} "
                  f"errors={len(errors)} dominant={n_dom}")
        except Exception as e:
            print(f"roofline_summary,0,unavailable({type(e).__name__})")

    if args.json:
        from repro.perf import flags
        payload = {
            "schema_version": SCHEMA_VERSION,
            "git_rev": _git_rev(),
            "machine": platform.machine(),
            "python": platform.python_version(),
            "util_engine": flags().util_engine,
            "total_seconds": round(sum(r["seconds"] for r in records), 6),
            "entries": records,
            "errors": errors,
        }
        if obs_by_section:
            payload["obs"] = obs_by_section
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {args.json} ({len(records)} entries, "
              f"{len(errors)} section errors)")

    if streamer is not None:
        streamer.emit("done", entries=len(records), errors=len(errors))
        streamer.close()

    failed = False
    if args.err_budget >= 0:
        bad = [r for r in records
               if r.get("max_rel_err", 0.0) > args.err_budget]
        if bad:
            names = {r["name"]: r["max_rel_err"] for r in bad}
            print(f"# FAIL: max_rel_err over budget {args.err_budget}: "
                  f"{names}", file=sys.stderr)
            failed = True
    if errors:
        print(f"# FAIL: {len(errors)} section(s) crashed: "
              f"{[e['section'] for e in errors]}", file=sys.stderr)
        failed = True
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
