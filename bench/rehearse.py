"""Rehearse a cell on the CPU: the whole of ``run.py`` but the look for a
chip, at a small size, with the Pallas probe in interpret mode.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <cell> [--seed N]
        [--seconds S] [--log2-rows K] [--trace 0|1]

Checks control flow and the comparison; its times say nothing about the
chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--log2-rows", type=int, default=9)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("rehearse: set JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(HERE.parent / ".jax_cache"))
    import run

    spec = run.load_spec()
    _, config, _ = run.load_cell(spec, args.workload)
    overrides = {
        "rows_per_relation": 2 ** args.log2_rows,
        "executor": {**config["executor"], "probe_backend": "pallas"},
    }
    result = run.run_cell(spec, args.workload, args.seed, args.seconds,
                          bool(args.trace), overrides=overrides)
    print(json.dumps(result))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
