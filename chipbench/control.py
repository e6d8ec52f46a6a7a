#!/usr/bin/env python3
"""The control of ``correct``: the plain reference with the configuration's
two-hop allocation left out (it states ``two_hop: true``), put in the
program's place and held to the reference as a run is.

    python chipbench/control.py --workload <cell> --seeds <n> [<n> ...] [--rounds <r>]

Each seed is one job's ``NEConfig.seed``: in a jobs cell give the
traffic's ``job_seeds``, and the control runs each job to its fixed point;
in a rounds cell it runs ``--rounds`` rounds, as many as a run's warm-up
and window hold.  Each seed prints the numbers ``run.py`` compares beside
their limits; the control has to come out as not correct on every seed.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import compare  # noqa: E402
import graphs  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def control_numbers(root: Path, workload: str, seed: int,
                    rounds: int | None = None) -> dict:
    spec = run.load_cell(root, workload)
    config = spec["config"]
    edges, n = graphs.build(config)
    ne = run.ne_fields(config, seed)
    d, p = config["num_devices"], ne["num_partitions"]
    mode = config["mode"]
    ctl = reference.Reference(edges, n, ne, d, mode=mode, two_hop=False)
    ref = reference.Reference(edges, n, ne, d, mode=mode)
    if spec["traffic"]["kind"] == "jobs":
        got, want = ctl.run(), ref.run()
        return compare.job_numbers(
            dict(state(got), **reference.stats(edges, got.edge_part, n, p)),
            want, reference.stats(edges, want.edge_part, n, p))
    if rounds is None:
        raise ValueError("a rounds cell needs --rounds")
    return compare.state_numbers(state(ctl.run(rounds)), ref.run(rounds))


def state(st) -> dict:
    return {"edge_part": st.edge_part, "vparts": st.vparts,
            "degree_rest": st.degree_rest,
            "edges_per_part": st.edges_per_part,
            "remaining": st.remaining, "rounds": st.rounds}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args()
    failed_all = True
    for seed in args.seeds:
        t = time.perf_counter()
        nums = control_numbers(run.ROOT, args.workload, seed, args.rounds)
        ok = compare.correct(nums)
        failed_all &= not ok
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": ok, "seconds": round(
                              time.perf_counter() - t, 3),
                          "checks": {k: {"value": v,
                                         "limit": compare.LIMITS[k]}
                                     for k, v in nums.items()}}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    raise SystemExit(main())
