"""BENCHMARK.json resolves to files, and the harness refuses a machine it
cannot measure on: a new cell, mix or layer metric is added as files."""
import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = run.load_cell(run.ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert configs[w["config"]]["file"] == \
            f"bench/configs/{w['config']}.json"
        assert cell.traffic["batch"] > 0 and cell.chips in (1, 4)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "qps"}
        assert cell.per_layer
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(configs)


def test_every_metric_has_its_reader(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    win = run.Window(batch=4, seconds=1.0, latencies=[0.25] * 4)
    for m in bench["end_to_end"]:
        assert run.end_to_end(m["name"], 1.0, win, 0.9, 10**9) > 0
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert callable(run.metric_reader(m["name"]))
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert any(x["name"] == m["moves"]
                       for x in run.load_cell(run.ROOT, cell).end_to_end)
    for name in os.listdir(os.path.join(run.BENCH, "metrics")):
        assert name[:-3] in {m["name"] for m in bench["per_layer"]}


def test_names_and_keys_keep_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")


def test_no_tpu_exits_nonzero_with_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(run.BENCH, "run.py"),
                        "--workload", "netflix.b16", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=run.ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def fake_devices(monkeypatch, kind, count=1):
    import jax

    devs = [SimpleNamespace(platform="tpu", device_kind=kind)] * count
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_unknown_device_kind_is_refused(monkeypatch):
    peaks = run._load_json(os.path.join(run.BENCH, "peaks.json"))
    fake_devices(monkeypatch, "TPU v99")
    with pytest.raises(run.Refused, match="not in bench/peaks.json"):
        run.chip(1, peaks)
    fake_devices(monkeypatch, "TPU v5 lite")
    with pytest.raises(run.Refused, match="needs 4 chips"):
        run.chip(4, peaks)
    assert run.chip(1, peaks) == {"platform": "tpu", "kind": "TPU v5 lite",
                                  "count": 1}
