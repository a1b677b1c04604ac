#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name: ``BENCHMARK.json`` at the root names the cell's configuration file
and its traffic; ``chipbench/traffic/<traffic>.json`` names the driver
(``chipbench/drivers/<driver>.py``); each per-layer metric is read by
``chipbench/layer_metrics/<name>.py``.  Adding any of them is adding a
file and an entry.

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``).  No chip, or fewer than the cell needs, is exit
code 2 and no result.  Extra flags, for the builder's own use: ``--sweep
r1,r2,..`` (serving: find the knee, no result line), ``--control
fp8|bf16`` (also read the reference computed in that lower precision, in
the program's place), ``--control kv_int8`` (serving: the engine itself
with int8 KV pages, which has to come out not ``correct``) and
``--keep-trace 1`` (leave the profiler's files and a description of them
under ``chiprun_out/``).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse      # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """``<root>/chipbench/<kind>/<name>.py``, loaded by its file name: a
    new generator, driver or reader is a new file, with no list to edit."""
    path = os.path.join(root, "chipbench", kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str):
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            break
    else:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {[c['name'] for c in bench['workloads']]})")
    for cfg in bench["configs"]:
        if cfg["name"] == cell["config"]:
            return cell, cfg
    raise SystemExit(f"workload {workload!r} names configuration "
                     f"{cell['config']!r}, which BENCHMARK.json lacks")


def metrics_for(bench: dict, group: str, cell_name: str, reported: dict):
    """The metrics of ``group`` that this cell is to report."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is not None and cell_name not in cells:
            continue
        if cells is None and group == "per_layer" and \
                m["moves"] not in reported:
            continue
        out.append(m)
    return out


def per_layer(bench, cell_name, run, root) -> dict:
    """Each per-layer metric of the cell through its own reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_for(bench, "per_layer", cell_name, run["e2e"]):
        reader = load_module(root, "layer_metrics", m["name"])
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, devices, options: dict | None = None,
             trace_dir: str | None = None, root: str = ROOT) -> dict | None:
    """Everything after the look for a chip.  Returns the result line as
    a dict (None for a sweep).  ``root`` is the checkout the cell's files
    are found in."""
    from chipbench.harness import xtrace
    from chipbench.harness.peaks import peaks

    cell, cfg_entry = find_cell(bench, workload)
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(root, "chipbench", "traffic",
                                     cell["traffic"] + ".json"))
    driver = load_module(root, "drivers", traffic["driver"])
    generator = load_module(root, "generators", traffic["generator"])
    trace_dir = trace_dir or os.path.join(root, "chiprun_out", "traces",
                                          f"{workload}-{seed}")
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "seed": int(seed), "seconds": float(seconds), "trace": trace,
           "devices": list(devices), "t_start": T_START,
           "options": options or {}, "trace_dir": trace_dir,
           "generator": generator}
    res = driver.run(ctx)
    if res is None:
        return None
    e2e_names = {m["name"] for m in
                 metrics_for(bench, "end_to_end", workload, {})}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    e2e = {k: {"value": float(v), "unit": units[k]}
           for k, v in res["metrics"].items() if k in e2e_names}
    missing = e2e_names - set(e2e)
    if missing:
        raise RuntimeError(f"cell {workload} did not produce {missing}")
    line = {"correct": bool(res["correct"]),
            "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": e2e,
            "device": res["device"]}
    if trace:
        run = dict(res["records"])
        run["e2e"] = res["metrics"]
        run["device"] = res["device"]
        run["peaks"] = peaks(res["device"]["kind"])
        xplane = xtrace.find_xplane(trace_dir)
        if ctx["options"].get("keep_trace"):
            with open(os.path.join(os.path.dirname(trace_dir),
                                   f"{workload}-{seed}.txt"), "w") as f:
                f.write(xtrace.describe(xplane))
        run["trace"] = xtrace.reduce(xplane)
        if not ctx["options"].get("keep_trace"):
            shutil.rmtree(trace_dir, ignore_errors=True)
        if run.get("trace_error"):
            raise RuntimeError(f"tracing failed: {run['trace_error']}")
        line["metrics"] = per_layer(bench, workload, run, root)
        line["device"]["busy_s"] = run["trace"]["busy_s"]
        line["device"]["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--control", default="", choices=("", "fp8", "bf16", "kv_int8"))
    ap.add_argument("--keep-trace", type=int, default=0,
                    help="1: leave the profiler's files on disk")
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _ = find_cell(bench, args.workload)

    from chipbench.harness import env
    try:
        devices = env.require_chips(int(cell["chips"]))
    except env.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    env.enable_compile_cache()
    options = {"control": args.control or None,
               "keep_trace": bool(args.keep_trace),
               "sample_trace": (os.path.join(ROOT, "chiprun_out",
                                             "sample_trace")
                                if args.keep_trace else None),
               "sweep": [float(r) for r in args.sweep.split(",") if r]}
    line = run_cell(bench, args.workload, args.seed, args.seconds,
                    bool(args.trace), devices, options)
    if line is not None:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
