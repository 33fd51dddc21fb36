"""Everything ``BENCHMARK.json`` names resolves to its file by name."""

from __future__ import annotations

import json

from conftest import BENCH, ROOT


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_cells_resolve_to_files():
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert (BENCH / "topologies" / f"{cfg['topology']}.py").is_file()
    for w in b["workloads"]:
        assert w["config"] in configs
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert mix["name"] == w["traffic"]
        lim = json.loads((BENCH / "limits" / f"{w['name']}.json")
                         .read_text())
        assert lim["hist_gap"] > 0 and lim["residual"] > 0
    for m in b["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}


def test_every_config_is_used_and_sizes_match():
    from traffic import demand
    b = _bench()
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        fab = demand.fabric(cfg, None)
        deg = {len(fab["edges"]) * 2 // fab["n"]}
        assert fab["n"] == cfg["routers"] and deg == {cfg["degree"]}
        assert cfg["terminals"] == cfg["routers"] * cfg["terminals_per_router"]


def test_device_kinds_have_peaks():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all("source" in v for v in peaks.values())
