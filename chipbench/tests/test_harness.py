"""The harness is driven by data: a new configuration, traffic shape and
per-layer metric are found by name as files alone; and BENCHMARK.json
keeps to the contract's names, units and shape."""
import json
import re
from pathlib import Path

import jax
import pytest

import minibench
import run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
LINE = re.compile(r"[^\n\t]{1,200}")


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    conf = dict(minibench.small_config("graph500_s16_p16", scale=9),
                name="g_new")
    root = minibench.make_root(tmp_path, [("g_new.short", conf,
                                           "rounds", 1)])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"][0]["traffic"] = "short_rounds"
    bench["per_layer"].append({
        "name": "traced_steps", "unit": "rounds", "better": "higher",
        "source": "program_span", "layer": "driver", "moves": "round_s",
        "workloads": ["g_new.short"]})
    e2e = [m for m in bench["end_to_end"] if m["name"] != "round_s"]
    bench["end_to_end"] = e2e + [{
        "name": "round_s", "unit": "s", "better": "lower", "bound": 0.05,
        "source": "host_clock", "workloads": ["g_new.short"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chipbench" / "traffic" / "short_rounds.json").write_text(
        json.dumps({"kind": "rounds", "warmup_rounds": 2,
                    "traced_rounds": 3}))
    (root / "chipbench" / "layers" / "traced_steps.py").write_text(
        "def read(ctx):\n"
        "    return float(ctx['trace'].count('step'))\n")
    jax.clear_caches()
    out = run.run_cell(root, "g_new.short", 5, 0.5, True,
                       require_tpu=False)
    assert out["correct"] and out["attempted"] == 3
    assert out["metrics"]["traced_steps"] == {"value": 3.0,
                                              "unit": "rounds"}
    out = run.run_cell(root, "g_new.short", 5, 0.5, False,
                       require_tpu=False)
    assert out["correct"]
    assert set(out["metrics"]) == {"round_s", "peak_hbm_bytes", "setup_s"}


def test_run_refuses_without_a_chip(capsys):
    assert run.main(["--workload", "graph500_s16.jobs", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_names_and_units():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([m["name"] for m in metrics]
             + [c["name"] for c in BENCH["configs"]]
             + [w[k] for w in BENCH["workloads"]
                for k in ("name", "config", "traffic")]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for name in names:
        assert NAME.fullmatch(name), name
    for m in metrics:
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for text in ([c["source"] for c in BENCH["configs"]]
                 + [x["why"] for x in BENCH["configs"] + BENCH["workloads"]]
                 + [m["layer"] for m in BENCH["per_layer"]]
                 + BENCH["command"]):
        assert LINE.fullmatch(text), text
    for group in (metrics, BENCH["configs"], BENCH["workloads"]):
        assert len({x["name"] for x in group}) == len(group)


def test_benchmark_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["chipbench"]
    configs = {c["name"]: c for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "chipbench" / "layers" / f"{m['name']}.py").exists()
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    cells = BENCH["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        conf = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        assert conf["num_devices"] == w["chips"]
        assert set(conf["ne"]) >= {"num_partitions", "alpha", "lam",
                                   "k_sel", "max_rounds", "sel_chunk",
                                   "edge_chunk", "two_hop", "use_pallas",
                                   "seed"}
        assert (ROOT / "chipbench" / "traffic"
                / f"{w['traffic']}.json").exists()
        reports = [m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                   if w["name"] in m.get("workloads", [w["name"]])]
        names = {m["name"] for m in reports}
        assert "setup_s" in names and len(names & set(e2e)) >= 2
        assert names - set(e2e)
    assert all(c["name"] in {w["config"] for w in cells}
               for c in BENCH["configs"])


@pytest.mark.parametrize("name", ["graph500_s16_p16", "graph500_s20_p16"])
def test_config_pins_every_neconfig_field(name):
    import dataclasses

    from repro.core.partitioner import NEConfig

    conf = json.loads((ROOT / "chipbench" / "configs"
                       / f"{name}.json").read_text())
    fields = {f.name for f in dataclasses.fields(NEConfig)}
    assert set(conf["ne"]) == fields
    assert conf["ne"]["use_pallas"] in (True, False)
    assert {"source", "assumed", "reduced", "guarantees"} <= set(conf)


def test_config_mode_reaches_the_driver(tmp_path):
    """The configuration's ``mode`` reaches ``PartitionDriver``: ``hybrid``
    asks for a HybridConfig, which no Graph500 configuration gives."""
    conf = dict(minibench.small_config("graph500_s16_p16", scale=8),
                name="g_hybrid", mode="hybrid")
    root = minibench.make_root(tmp_path, [("g_hybrid.jobs", conf, "jobs",
                                           1)])
    with pytest.raises(TypeError, match="HybridConfig"):
        run.run_cell(root, "g_hybrid.jobs", 1, 0.5, False,
                     require_tpu=False)
