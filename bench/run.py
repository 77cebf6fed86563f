"""Run one benchmark cell once and print its result as one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name, so a new cell, configuration, mix or metric
is new files and entries, never an edit.  A cell of ``BENCHMARK.json``
names a configuration (``bench/configs/<config>.json``: its clients'
queries, sizes and data generator ``bench/generators/<name>.py``) and a
traffic mix (``bench/traffic/<mix>.json``: parameters for the driver it
names, ``bench/drivers/<driver>.py``).  Every metric, end-to-end and
per-layer, is read by ``bench/metrics/<metric>.py``.

Set-up builds every data version from ``--seed``, places it on the device
and runs the mix's warm-up ticks, which compile every shape the window
uses.  The window then drives the service for ``--seconds``, finishing
the tick in flight.  After it, the outputs of a seeded sample of the
window's ticks are compared with the NumPy reference of the data versions
each tick read.  ``--trace 1`` records the window with the JAX profiler
and reports the per-layer metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

#: ticks of the window whose outputs are kept for the comparison
CHECK_TICKS = 8
#: JAX monitoring events: an executable built by the backend, and one
#: loaded from the persistent compilation cache
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(spec: dict, name: str) -> tuple[dict, dict, dict]:
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def named(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = HERE / kind / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class CompileLog:
    """Executables built (backend compiles) or loaded from the persistent
    cache, counted from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.built = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == CACHE_HIT:
            self.built += 1

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE:
            self.built += 1


def mismatched_rows(got: np.ndarray, want: np.ndarray) -> int:
    """Rows in one of two sorted unique row sets and not in the other."""
    if got.size == 0 or want.size == 0:
        return int(len(got) + len(want))
    _, counts = np.unique(np.concatenate([got, want]), axis=0, return_counts=True)
    return int((counts == 1).sum())


def compare(kept, versions_np, clients) -> dict:
    """Compare every output of the kept ticks' requests with the reference
    for the data versions each tick read."""
    import reference

    cache: dict = {}
    rows = mismatched = checked = wrong = 0
    for tk in kept:
        key = tuple(sorted(tk.versions.items()))
        db = {r: versions_np[k][r] for r, k in tk.versions.items()}
        for c, outputs in zip(tk.clients, tk.outputs):
            if outputs is None:
                continue
            if (key, c) not in cache:
                cache[(key, c)] = reference.eval_request(db, clients[c])
            want = cache[(key, c)]
            bad = sum(
                mismatched_rows(outputs.get(name, np.zeros((0, w.shape[1]), np.int32)), w)
                for name, w in want.items()
            )
            checked += 1
            wrong += bad > 0
            mismatched += bad
            rows += sum(len(w) for w in want.values())
    return {"checked": checked, "wrong": wrong, "mismatched": mismatched, "rows": rows}


def run_cell(spec: dict, name: str, seed: int, seconds: float, trace: bool,
             *, overrides: dict | None = None, server_factory=None) -> dict:
    """Set up, measure and check one cell; returns the result line.  The
    caller has checked the device."""
    import jax

    from datagen import clients as client_queries, make_versions

    cell, config, traffic = load_cell(spec, name)
    config = {**config, **(overrides or {})}
    if server_factory is None:
        import sut

        sut.enable_compile_cache()
        server_factory = sut.ProgramServer

    compiles = CompileLog()
    dev = jax.devices()[0]
    versions_np = make_versions(config, traffic["versions"], seed)
    clients = client_queries(config)
    server = server_factory(config, versions_np)
    driver = named("drivers", traffic["driver"]).Driver(
        server, traffic, versions_np[0].keys())
    driver.warm()
    setup_s = time.perf_counter() - T0

    counters_before = server.counters()
    built_before = compiles.built
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    rng = np.random.default_rng([seed % 2**63, 7])
    start, ticks, kept = driver.window(seconds, CHECK_TICKS, rng)
    end = max(max(t.done) for t in ticks)
    if trace:
        jax.profiler.stop_trace()
    window_compiles = compiles.built - built_before
    counters_after = server.counters()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    for tk in kept:
        tk.outputs = [server.fetch(r) if ok else None
                      for r, ok in zip(tk.requests, tk.ok)]
        tk.requests = []
    server.close()
    del server, driver
    gc.collect()

    latencies = [x for t in ticks for x in t.latencies]
    attempted = sum(len(t.ok) for t in ticks)
    failed_requests = sum(not ok for t in ticks for ok in t.ok)
    window_s = end - start

    ctx = SimpleNamespace(
        ticks=ticks, window_s=window_s, window_compiles=window_compiles,
        counters_before=counters_before, counters_after=counters_after,
        device_kind=dev.device_kind, trace=None, busy_s=0.0,
        setup_s=setup_s, peak_bytes=peak,
    )
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices()), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        import trace_reduce

        ctx.trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.busy_s = trace_reduce.busy_s(ctx.trace)
        result_device.update(busy_s=ctx.busy_s, window_s=window_s)
        breakdown = {"device_ops": trace_reduce.top_ops(ctx.trace),
                     "idle_gaps": trace_reduce.idle_gaps(ctx.trace)}

    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        if applies(m, name):
            value = named("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    cmp = compare(kept, versions_np, clients)
    checks = {
        "mismatched_rows": {"value": cmp["mismatched"], "limit": 0},
        "failed_requests": {"value": failed_requests, "limit": 0},
        "checked_requests": {"value": cmp["checked"], "at_least": 1},
    }
    correct = (cmp["mismatched"] <= 0 and failed_requests <= 0
               and cmp["checked"] >= 1)
    walls = sorted(max(t.done) - min(t.submitted) for t in ticks)
    print(
        f"{name} seed {seed}: setup {setup_s} s, {len(ticks)} ticks, "
        f"{len(latencies)} requests in {window_s} s, tick walls min "
        f"{walls[0]} median {walls[len(walls) // 2]} max {walls[-1]} s, "
        f"{window_compiles} executables built in the window; checked "
        f"{cmp['checked']} requests of {len(kept)} ticks ({cmp['rows']} rows)",
        file=sys.stderr,
    )
    for key, chk in checks.items():
        limit = (f"limit {chk['limit']}" if "limit" in chk
                 else f"at least {chk['at_least']}")
        print(f"check {key}: {chk['value']} ({limit})", file=sys.stderr)
    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed_requests + cmp["wrong"],
        "metrics": metrics,
        "device": result_device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_spec()
    cell, _, _ = load_cell(spec, args.workload)
    # the compile cache lives in the checkout, at a fixed path: the
    # program's compile_cache.enable() keeps a directory set here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # no size limit: with one, every write scans the whole directory to
    # evict, and a cold set-up of ~10^3 executables spends most of its
    # time there; the checkout's cache holds about 10^2 MB
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    # libtpu would otherwise write its logs to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    result = run_cell(spec, args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
