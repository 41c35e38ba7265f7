"""BENCHMARK.json against the rules its readers rely on: keys, names,
units, files found by name, and the time a full check of every cell
needs."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_names_units_and_entries(bench):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for item in bench[group]:
            assert NAME.match(item["name"]), item["name"]
            assert (group, item["name"]) not in seen
            seen.add((group, item["name"]))
            if "unit" in item:
                assert UNIT.match(item["unit"]), item["unit"]
                assert item["better"] in ("lower", "higher")
                assert item["source"] in SOURCES
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", f"{w['traffic']}.json"))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"])


def test_every_cell_reports_enough(bench):
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == configs
    assert len({(w["config"], w["traffic"])
                for w in bench["workloads"]}) == len(cells)

    def reports(m, cell):
        return "workloads" not in m or cell in m["workloads"]

    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for cell in cells:
        mine = {n for n, m in e2e.items() if reports(m, cell)}
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert any(reports(m, cell) for m in bench["per_layer"]), cell
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(e2e[m["moves"]], cell)
        stem = m["name"].split(".")[0]
        assert any(os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                               f"{s}.py"))
                   for s in (m["name"], stem)), m["name"]


def _load(kind, name):
    with open(os.path.join(ROOT, "bench", kind, f"{name}.json")) as f:
        return json.load(f)


def test_generators_and_scenarios_are_found_by_name(bench):
    """Each config's corpus generator and each traffic's scenarios exist
    as the harness looks them up, with the names the harness calls."""
    import inspect

    from bench import byname, ops, scenarios
    from repro.logstore.store import DynaWarpStore
    open_keys = set(inspect.signature(DynaWarpStore.open).parameters)
    for c in bench["configs"]:
        cfg = _load("configs", c["name"])
        gen = cfg["corpus"].get("generator")
        if gen is not None:
            assert callable(byname.load("corpora", gen).generate), gen
        assert set(cfg.get("open", {})) <= open_keys - {"cls", "path"}
    for traffic in {w["traffic"] for w in bench["workloads"]}:
        t = _load("traffic", traffic)
        for scenario in t["pool"]:
            if scenario not in scenarios.OPS:
                mod = scenarios.module(scenario)
                assert isinstance(mod.OP, str) and callable(mod.draw)
                op = ops.of_module(mod)
                assert callable(op.tokens) and callable(op.send), scenario
                assert op.answer is None or callable(op.answer), scenario


def test_full_check_fits_at_24_cells(bench):
    runs = 2 + 14 * 24
    need = runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert need <= 43200

