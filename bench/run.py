"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
(``bench/configs/<config>.json``), traffic (``bench/traffic/<traffic>.json``)
and cell file (``bench/workloads/<cell>.json``, which names the driver in
``bench/drivers/`` and the check's limits) are found by name.  With
``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, each read by
``bench/metrics/<metric>.py`` from one profiled unit.  The run exits
non-zero, printing no result, without enough TPU chips.

A new deployment enters as new files and appended entries, with no edit
to a file that is here:

* a configuration, ``bench/configs/<config>.json`` (pods, window, policy;
  for an RL policy an ``agent`` block with ``window``, ``c_max``, the
  frozen ``params`` and, optionally, ``obs_context`` and ``planner``);
* a traffic file, ``bench/traffic/<traffic>.json``, whose ``process``
  names ``bench/lib/processes/<process>.py``: a new file only where the
  arrival shape is new (it may give each arrival a slice width);
* a reference planner, ``bench/reference/<planner>.py`` with a class
  ``Planner``, only where the agent sees more than the window's profiles
  (it is handed the pod's view when the window forms);
* a cell file, ``bench/workloads/<cell>.json``;
* entries appended to ``BENCHMARK.json``: the configuration, the cell,
  and the cell's name in the ``workloads`` of the metrics it reports.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_cell(name: str) -> tuple[dict, dict]:
    """The ``BENCHMARK.json`` entry of the cell and the whole file."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == name:
            return w, bench
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def per_layer_names(bench: dict, cell: str) -> list[str]:
    """Per-layer metrics this cell reports: those that list it, and those
    without a list whose end-to-end metric the cell reports."""
    e2e = set(e2e_names(bench, cell))
    return [m["name"] for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def e2e_names(bench: dict, cell: str) -> list[str]:
    return [m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, run: dict):
    spec = importlib.util.spec_from_file_location(
        "bench_metric", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


class Context:
    """What a driver gets: the cell's files, the run's arguments, and the
    harness's hooks around its measured window."""

    def __init__(self, args, entry: dict, device: dict):
        self.name = args.workload
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.device = device
        self.config = _json(BENCH / "configs" / f"{entry['config']}.json")
        self.traffic = _json(BENCH / "traffic" / f"{entry['traffic']}.json")
        self.spec = _json(BENCH / "workloads" / f"{self.name}.json")
        from bench.lib.harness import CompileWatch, Profiler, Spans

        self.watch = CompileWatch()
        self.spans = Spans(self.trace)
        self.profiler = Profiler() if self.trace else None
        self.trace_path = None
        self.setup_s = None
        self.window = None
        self.memory_peak_bytes = 0
        self._ann = None

    def window_start(self) -> None:
        """Set-up ends here; a traced run starts its profiler."""
        self.setup_s = time.perf_counter() - T_START
        if self.profiler is not None:
            import jax

            self.profiler.start()
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()

    def first_unit_done(self) -> None:
        """A traced run traces the window's first unit only."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
            self.trace_path = self.profiler.stop()

    def window_end(self, compiles: dict) -> None:
        from bench.lib.harness import memory_peak_bytes

        self.window = compiles
        self.memory_peak_bytes = memory_peak_bytes(self.device["count"])


def run_cell(ctx: Context, bench: dict) -> dict:
    """Drive the cell and build its result line (without the device
    check, which ``main`` makes first)."""
    driver = importlib.import_module(f"bench.drivers.{ctx.spec['driver']}")
    try:
        out = driver.run(ctx)
        breakdown = None
        if ctx.trace:
            from bench.lib import trace as trace_lib

            breakdown = trace_lib.reduce(*trace_lib.read(ctx.trace_path))
    finally:
        if ctx.profiler is not None:
            ctx.profiler.close()
    limits = ctx.spec["check"]["limits"]
    check = {k: {"value": v, "limit": limits[k]}
             for k, v in out["numbers"].items()}
    correct = (out["failed"] == 0
               and all(c["value"] <= c["limit"] for c in check.values()))
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    units.update({m["name"]: m["unit"] for m in bench["per_layer"]})
    if ctx.trace:
        run = {"breakdown": breakdown, "counters": out["counters"],
               "device": ctx.device}
        values = {n: read_metric(n, run)
                  for n in per_layer_names(bench, ctx.name)}
        metrics = {n: {"value": v, "unit": units[n]}
                   for n, v in values.items() if v is not None}
    else:
        values = dict(out["metrics"], setup_s=ctx.setup_s)
        metrics = {n: {"value": values[n], "unit": units[n]}
                   for n in e2e_names(bench, ctx.name)}
    device = {k: ctx.device[k] for k in ("platform", "kind", "count")}
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if ctx.trace:
        device["busy_s"] = breakdown["busy_s"]
        device["window_s"] = breakdown["window_s"]
        line["breakdown"] = {"device_ops": breakdown["device_ops"],
                             "idle_gaps": breakdown["idle_gaps"]}
    line["check"] = check
    return {"line": line, "examples": out["examples"],
            "counters": out["counters"], "window": ctx.window}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    entry, bench = load_cell(args.workload)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bench.lib.harness import NoChip, device_info

    try:
        device = device_info(entry["chips"])
    except NoChip as e:
        print(f"not run: {e}", file=sys.stderr)
        return 2
    out = run_cell(Context(args, entry, device), bench)
    w = out["window"]
    print(f"window: {w['traces']} traces, {w['backend_compiles']} backend "
          f"compiles, {w['compile_s']:.6f} s compiling (persistent cache "
          f"{w['cache_hits']} hits, {w['cache_misses']} misses in the run)")
    print("counters: " + json.dumps(out["counters"]))
    for e in out["examples"]:
        print(f"differs: {e}", file=sys.stderr)
    for k, c in out["line"]["check"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["line"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
