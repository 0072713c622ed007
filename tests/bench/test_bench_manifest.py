"""``BENCHMARK.json`` keeps to the benchmark's contract, and every file a
cell needs is found by name."""
import json
import math
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bench import harness  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    M = json.load(_fh)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
WIDTH = re.compile(r"hidden_size|intermediate_size|latent|state_size|d_state|"
                   r"proj|_dim$|_rank$|expansion|expand|per_tok|d_model|"
                   r"d_ff|n_embd|width")
CELLS = [w["name"] for w in M["workloads"]]


def line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_size():
    assert sorted(M) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_command_and_paths():
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    cmd = M["command"]
    assert 1 <= len(cmd) <= 32 and all(line_ok(w) for w in cmd)
    for w in cmd:
        if os.path.exists(os.path.join(REPO, w)):
            assert any(w.startswith(p + "/") for p in M["paths"]), w


def test_run_seconds_fit_a_full_check_of_24_cells():
    rs = M["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_lines():
    seen = set()
    for m in M["end_to_end"] + M["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in M["configs"]:
        assert NAME.match(c["name"]) and line_ok(c["why"])
        assert line_ok(c["source"]) and sorted(c) == sorted(
            ["name", "source", "file", "reduced", "why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and not WIDTH.search(k)
                   for k in c["reduced"])
    assert len(set(CELLS)) == len(CELLS)
    pairs = {(w["config"], w["traffic"]) for w in M["workloads"]}
    assert len(pairs) == len(CELLS)
    for w in M["workloads"]:
        assert sorted(w) == sorted(["name", "config", "traffic", "chips",
                                    "why"])
        assert NAME.match(w["traffic"]) and line_ok(w["why"])
        assert w["chips"] in (1, 4)


def test_four_chip_cells_within_their_share():
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, math.floor(0.5 * len(CELLS)))


def test_end_to_end_bounds_and_sources():
    names = [m["name"] for m in M["end_to_end"]]
    assert "setup_s" in names and 1 <= len(names) <= 16
    for m in M["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line_ok(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]]
        for w in m.get("workloads", CELLS):
            assert w in CELLS
            assert w in moved.get("workloads", CELLS)
        assert callable(harness.metric_reader(m["name"]).read)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = harness.resolve(name, REPO)
    assert cell.driver().run and cell.reference().layout
    reported = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    entry = {c["name"]: c for c in M["configs"]}[
        {w["name"]: w for w in M["workloads"]}[name]["config"]]
    assert any(entry["file"].startswith(p + "/") for p in M["paths"])
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"])
    assert set(cell.settings["limits"]) == set(
        {"train": ["loss_gap", "grad_gap", "change_gap"],
         "serve": ["served_gap"]}[cell.traffic["kind"]])
