import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell:  with the production mesh, ``jit(step).lower(*ShapeDtype
Structs).compile()`` must succeed; we record memory_analysis (proves the
per-device footprint fits a v5e), cost_analysis (FLOPs/bytes for the
roofline) and the parsed collective schedule.  Results are written
incrementally to results/dryrun/<cell>.json so reruns skip finished cells.

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402

from repro.configs.registry import ARCH_IDS, all_cells, get_arch  # noqa: E402
from repro.dist.context import mesh_context  # noqa: E402
from repro.launch.hlo import collective_bytes, roofline  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import make_step  # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run_cell(arch_id: str, shape_id: str, multi_pod: bool,
             rules_override=None, tag: str = "") -> dict:
    spec = get_arch(arch_id)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    t0 = time.time()
    with mesh_context(mesh, batch_axes=batch_axes, model_axis="model"), \
            jax.sharding.set_mesh(mesh):
        if spec.family == "lm" and rules_override is not None:
            from repro.launch.steps import make_lm_step
            bundle = make_lm_step(spec.config,
                                  dict(__import__("repro.configs.shapes",
                                                  fromlist=["FAMILY_SHAPES"])
                                       .FAMILY_SHAPES["lm"][shape_id]),
                                  mesh, multi_pod, rules=rules_override)
        else:
            bundle = make_step(spec, shape_id, mesh=mesh,
                               multi_pod=multi_pod)
        jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                         donate_argnums=bundle.donate_argnums)
        lowered = jitted.lower(*bundle.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    rl = roofline(cost, coll, n_chips, bundle.model_flops,
                  bundle.loop_scale)
    rec = {
        "arch": arch_id, "shape": shape_id,
        "mesh": list(mesh.devices.shape), "chips": n_chips,
        "multi_pod": multi_pod, "tag": tag,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed",
                                          "transcendentals")},
        "collectives": coll,
        "roofline": rl.as_dict(),
        "meta": bundle.meta,
    }
    return rec


def cell_path(arch_id, shape_id, multi_pod, tag="") -> Path:
    pod = "pod2" if multi_pod else "pod1"
    sfx = f"-{tag}" if tag else ""
    return RESULTS / f"{arch_id}__{shape_id}__{pod}{sfx}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    RESULTS.mkdir(parents=True, exist_ok=True)
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch_id, shape_id in cells:
        for mp in meshes:
            out = cell_path(arch_id, shape_id, mp)
            if out.exists() and not args.force:
                print(f"skip {out.name}")
                continue
            print(f"=== {arch_id} × {shape_id} × "
                  f"{'2x16x16' if mp else '16x16'} ===", flush=True)
            try:
                rec = run_cell(arch_id, shape_id, mp)
                out.write_text(json.dumps(rec, indent=1))
                r = rec["roofline"]
                print(f"  ok: compile={rec['compile_s']}s "
                      f"mem={rec['memory']['peak_bytes']/1e9:.2f}GB "
                      f"dom={r['dominant']} "
                      f"t=({r['compute_s']:.2e},{r['memory_s']:.2e},"
                      f"{r['collective_s']:.2e})s", flush=True)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures.append((arch_id, shape_id, mp, repr(e)))
                print(f"  FAIL {e}\n{traceback.format_exc()[-2000:]}",
                      flush=True)
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
