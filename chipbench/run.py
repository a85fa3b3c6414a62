"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's root:
the cell gives its configuration and traffic mix; the configuration's file
(``configs/``) gives its ``kind``, whose runner is ``kinds/<kind>.py``, and
its ``arch``, whose module ``archs/<arch>.py`` holds all that knows the
model's shape; the traffic mix is ``traffic/<traffic>.json``; each
per-layer metric is read by ``metrics/<name>.py``, whose ``read(ctx)``
returns a number or ``None`` (nothing to read: the metric is left out of
the line).

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the device's busy and window seconds and
a breakdown from the profiler's trace.  The numbers that decide ``correct``
are printed beside their limits as the last lines of standard error and
under ``checks``, the last key of the line.  Exits 2, printing no result,
when JAX finds no TPU or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def load_cell(name: str, root: str = ROOT):
    """(benchmark, cell, configuration dict, traffic dict) for ``name``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, cfg, traffic


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}

    def applies(m):
        if "workloads" in m:
            return cell["name"] in m["workloads"]
        return m["moves"] in names

    return [m for m in bench["per_layer"] if applies(m)]


def _reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _plain(x):
    """A number JSON can carry: a non-finite float becomes its name."""
    return x if not isinstance(x, float) or math.isfinite(x) else str(x)


def measure(bench, cell, cfg, traffic, *, seed: int, seconds: float,
            trace: bool, devices, t_start: float) -> dict:
    """Run the cell on ``devices`` and assemble the result line."""
    from chipbench import compare, device
    from chipbench import trace as tracing

    kind = importlib.import_module(f"chipbench.kinds.{cfg['kind']}")
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        out = kind.run(cfg, traffic, seed=seed, seconds=seconds,
                       trace_dir=trace_dir, t_start=t_start, devices=devices)
        dev = device.describe(devices)
        dev["memory_peak_bytes"] = out["device_peak"]
        result = {"correct": compare.passed(out["checks"]),
                  "attempted": out["attempted"], "failed": out["failed"]}
        metrics = {}
        if trace:
            red = tracing.reduce_planes(tracing.load(trace_dir), out["spans"])
            ctx = dict(out["ctx"], trace=red,
                       peaks=device.peaks(dev["kind"]))
            for m in cell_metrics(bench, cell, True):
                value = _reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            dev["busy_s"], dev["window_s"] = red.busy_s, red.window_s
            result["breakdown"] = {
                "device_ops": [list(x) for x in red.device_ops],
                "idle_gaps": [list(x) for x in red.idle_gaps]}
        else:
            # A metric's name is the runner's quantity, optionally followed
            # by a dot and the cells it is bounded for.
            for m in cell_metrics(bench, cell, False):
                metrics[m["name"]] = {
                    "value": out["e2e"][m["name"].split(".")[0]],
                    "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dev
        result["checks"] = {k: {"value": _plain(c["value"]),
                                "limit": _plain(c["limit"])}
                            for k, c in out["checks"].items()}
        return dict(result=result, checks=out["checks"], info=out["info"])
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic = load_cell(args.workload)
    from chipbench import archs, compare, device
    archs.load(cfg)  # a missing module stops the run before the chip
    try:
        devices = device.require_tpu(cell["chips"])
    except device.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    run = measure(bench, cell, cfg, traffic, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  devices=devices, t_start=T_START)
    result = run["result"]
    print(f"chipbench: {json.dumps(run['info'])}", file=sys.stderr)
    for line in compare.lines(run["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
