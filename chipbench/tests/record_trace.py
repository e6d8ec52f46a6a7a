"""Record the small trace the reduction's test reads, on the chip.

    python chipbench/tests/record_trace.py --out <dir>

Runs a rounds cell at test size (Graph500 scale 12, one chip, four traced
rounds) through the harness with ``--trace 1`` and copies its
``.xplane.pb`` to ``<dir>/rounds_s12.xplane.pb``; the test expects it at
``chipbench/testdata/``.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import minibench  # noqa: E402
import run  # noqa: E402
import xplane  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    with tempfile.TemporaryDirectory(dir=HERE.parents[1]) as tmp:
        conf = minibench.small_config("graph500_s16_p16", scale=12)
        root = minibench.make_root(Path(tmp), [("small.rounds", conf,
                                                "rounds", 1)])
        out = run.run_cell(root, "small.rounds", 12, 1, True)
        print(out, flush=True)
        src = xplane.find(str(root / ".chipbench" / "trace"
                              / "small.rounds"))
        dst = Path(args.out) / "rounds_s12.xplane.pb"
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, dst)
        print(dst, dst.stat().st_size)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
