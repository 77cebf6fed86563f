"""The control of the comparison: the reference in the program's place,
with the configuration's freshness guarantee broken.

``StaleReference`` answers every tick from the data versions of the tick
before it, as a result cache that ignored re-registration would.  Driven
through the same closed loop and the same comparison as a benchmark run,
it must come out not correct; its ``mismatched_rows`` readings are the
upper readings of that check's limit (PERF.md).  The benchmark's own runs
never run it.

    python bench/control.py --workload <cell> --seeds 1 2 3 [--seconds S]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import reference  # noqa: E402
from datagen import clients  # noqa: E402


class _Request:
    def __init__(self, client: int):
        self.client = client
        self.outputs: dict = {}


class StaleReference:
    """Server interface of ``sut.ProgramServer``, answered by the NumPy
    reference one data version behind."""

    def __init__(self, config: dict, versions: list[dict]):
        self.versions = versions
        self.clients = clients(config)
        self.current = {r: 0 for r in versions[0]}
        self.stale = dict(self.current)
        self.pending: list[_Request] = []
        self._cache: dict = {}

    def refresh(self, names, k: int) -> None:
        self.stale = dict(self.current)
        for name in names:
            self.current[name] = k

    def submit(self, client: int) -> _Request:
        req = _Request(client)
        self.pending.append(req)
        return req

    def tick(self) -> None:
        key = tuple(sorted(self.stale.items()))
        db = {r: self.versions[k][r] for r, k in self.stale.items()}
        for req in self.pending:
            if (key, req.client) not in self._cache:
                self._cache[(key, req.client)] = reference.eval_request(
                    db, self.clients[req.client])
            req.outputs = self._cache[(key, req.client)]
        self.pending = []
        self.stale = dict(self.current)

    @staticmethod
    def wait(req) -> bool:
        return True

    @staticmethod
    def fetch(req) -> dict:
        return req.outputs

    def counters(self) -> dict:
        return {"result_hits": 0, "result_lookups": 0}

    def last_report(self):
        return None

    def close(self) -> None:
        self._cache = {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--log2-rows", type=int, default=None,
                    help="rows per relation; default: the configuration's")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(HERE.parent / ".jax_cache"))
    import run

    spec = run.load_spec()
    overrides = ({"rows_per_relation": 2 ** args.log2_rows}
                 if args.log2_rows is not None else None)
    readings = []
    for seed in args.seeds:
        result = run.run_cell(spec, args.workload, seed, args.seconds, False,
                              overrides=overrides, server_factory=StaleReference)
        readings.append({"seed": seed, "correct": result["correct"],
                         **{k: v["value"] for k, v in result["checks"].items()}})
        print(json.dumps(readings[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "control": "stale",
                      "readings": readings}))
    return 0 if all(not r["correct"] for r in readings) else 1


if __name__ == "__main__":
    sys.exit(main())
