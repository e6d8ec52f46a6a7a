import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""§Perf hillclimb harness: compile a cell variant, derive scope-corrected
roofline terms, write a tagged JSON next to the baselines.

  python -m repro.launch.hillclimb --cell deepseek_train --variant <name>
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402

from repro.configs.registry import get_arch  # noqa: E402
from repro.configs.shapes import FAMILY_SHAPES  # noqa: E402
from repro.dist.context import mesh_context  # noqa: E402
from repro.launch.hlo import (ICI_BW, collective_bytes_scoped,  # noqa: E402
                              roofline)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import make_gnn_step, make_lm_step  # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def compile_and_measure(bundle, mesh, n_chips):
    t0 = time.time()
    jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     donate_argnums=bundle.donate_argnums)
    compiled = jitted.lower(*bundle.args).compile()
    dt = time.time() - t0
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    scoped = collective_bytes_scoped(hlo, bundle.loop_scale)
    rl = roofline(cost, scoped["total_scaled"], n_chips,
                  bundle.model_flops, loop_scale=1)  # bytes pre-scaled
    # memory term still needs the loop scale on HLO bytes:
    mem_s = float(cost.get("bytes accessed", 0.0)) * bundle.loop_scale \
        / 819e9
    coll_s = sum(scoped["total_scaled"].values()) / ICI_BW
    return {
        "compile_s": round(dt, 1),
        "mem_peak_gb": round(((mem.argument_size_in_bytes or 0)
                              + (mem.temp_size_in_bytes or 0)) / 1e9, 2),
        "compute_s": rl.compute_s,
        "memory_s": mem_s,
        "collective_s": coll_s,
        "collectives_entry": scoped["entry"],
        "collectives_loop": scoped["loop"],
        "loop_scale": bundle.loop_scale,
    }


def lm_cell(arch, shape_id, multi_pod=False, **overrides):
    spec = get_arch(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ba = ("pod", "data") if multi_pod else ("data",)
    shape = dict(FAMILY_SHAPES["lm"][shape_id])
    with mesh_context(mesh, ba, "model"), jax.sharding.set_mesh(mesh):
        b = make_lm_step(spec.config, shape, mesh, multi_pod, **overrides)
        return compile_and_measure(b, mesh, mesh.size)


def gnn_cell(arch, shape_id, multi_pod=False, **overrides):
    spec = get_arch(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ba = ("pod", "data") if multi_pod else ("data",)
    shape = dict(FAMILY_SHAPES["gnn"][shape_id])
    with mesh_context(mesh, ba, "model"), jax.sharding.set_mesh(mesh):
        b = make_gnn_step(spec, spec.config, shape, mesh, multi_pod,
                          **overrides)
        return compile_and_measure(b, mesh, mesh.size)


EXPERIMENTS = {
    # cell A: deepseek-67b × train_4k (most collective-bound)
    "dsk_base": lambda: lm_cell("deepseek-67b", "train_4k"),
    "dsk_mb1": lambda: lm_cell("deepseek-67b", "train_4k", mb_override=1),
    "dsk_mb2": lambda: lm_cell("deepseek-67b", "train_4k", mb_override=2),
    "dsk_dots": lambda: lm_cell("deepseek-67b", "train_4k",
                                remat_override="dots"),
    # cell B: kimi-k2 × train_4k (worst roofline fraction, memory-bound)
    "kimi_base": lambda: lm_cell("kimi-k2-1t-a32b", "train_4k"),
    "kimi_mb1": lambda: lm_cell("kimi-k2-1t-a32b", "train_4k",
                                mb_override=1),
    "kimi_mb2": lambda: lm_cell("kimi-k2-1t-a32b", "train_4k",
                                mb_override=2),
    # cell C: gin-tu × ogb_products (the paper's own technique: partition
    # quality sets the engine's collective term)
    "gin_rf4": lambda: gnn_cell("gin-tu", "ogb_products", engine_rf=4.0),
    "gin_rf21": lambda: gnn_cell("gin-tu", "ogb_products", engine_rf=2.1),
    "gin_rf21_bf16": lambda: gnn_cell("gin-tu", "ogb_products",
                                      engine_rf=2.1, sync_dtype="bfloat16"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", choices=list(EXPERIMENTS), required=True)
    args = ap.parse_args()
    rec = EXPERIMENTS[args.exp]()
    out = RESULTS / f"hillclimb__{args.exp}.json"
    out.write_text(json.dumps(rec, indent=1))
    print(json.dumps({args.exp: {k: v for k, v in rec.items()
                                 if not k.startswith("collectives")}},
                     indent=1))


if __name__ == "__main__":
    main()
