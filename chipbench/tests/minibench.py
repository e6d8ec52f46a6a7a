"""A benchmark root at test size: the real traffic, layers and peaks, and
small configurations of the real ones, in a temporary directory."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
REAL = HERE.parent


def small_config(name: str, **graph) -> dict:
    conf = json.loads((HERE / "configs" / f"{name}.json").read_text())
    conf["graph"].update(graph)
    return conf


def make_root(tmp: Path, cells: list[tuple]) -> Path:
    """``cells``: (cell name, config dict, traffic, chips)."""
    bench = json.loads((REAL / "BENCHMARK.json").read_text())
    (tmp / "chipbench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "layers"):
        shutil.copytree(HERE / sub, tmp / "chipbench" / sub)
    peaks = json.loads((HERE / "peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test: v5e numbers")
    (tmp / "chipbench" / "peaks.json").write_text(json.dumps(peaks))
    kind = {w["name"]: (w["traffic"], w["chips"])
            for w in bench["workloads"]}
    bench["configs"], bench["workloads"] = [], []
    for name, conf, traffic, chips in cells:
        file = f"chipbench/configs/{conf['name']}.json"
        (tmp / file).write_text(json.dumps(conf))
        if conf["name"] not in [c["name"] for c in bench["configs"]]:
            bench["configs"].append({"name": conf["name"], "source": "test",
                                     "file": file, "reduced": [],
                                     "why": "test"})
        bench["workloads"].append({"name": name, "config": conf["name"],
                                   "traffic": traffic, "chips": chips,
                                   "why": "test"})
    # a metric applies to the test cells of the traffic and chips of the
    # real cells it names
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            like = {kind[w] for w in m["workloads"]}
            m["workloads"] = [w["name"] for w in bench["workloads"]
                              if (w["traffic"], w["chips"]) in like]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
