"""The benchmark's one command.

    python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run is one new process: build from the seed, warm up, measure
``--seconds``, print the contract's one JSON line last, exit. Everything is
found by name from ``BENCHMARK.json``: the cell's configuration
(``configs/<config>.json``, which names its ``runner``), its traffic
(``traffic/<traffic>.json``), and each metric's definition
(``end_to_end/<metric>.json`` or ``layer_metrics/<metric>.json``, which names
its ``reader`` and the reader's arguments). With ``--trace 0`` the line
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics and the device's busy time and breakdown from a profiler slice.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result line. There is no CPU fallback and no switch for one;
tests call the runners with a tiny configuration instead.

``--sweep r1,r2,...`` (serving cells; a tool for the builder, not part of
the contract) offers the cell's traffic at each of those request rates after
one set-up and prints a ``SWEEP`` line for each instead of a result line: how
the knee in the traffic file was found. Trust the first rate of a call only:
on today's program a second load phase in one process runs out of device
memory (PERF.md, Open questions), so sweep with one call per rate.
"""

from __future__ import annotations

import time

T_START = time.monotonic()          # set-up is counted from here

import argparse                      # noqa: E402
import importlib                     # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

from . import harness, trace_reduce  # noqa: E402

ROOT = os.path.dirname(harness.HERE)
_KIND_DIR = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def read_metrics(manifest, kind: str, workload: str, record) -> dict:
    """Every metric of ``kind`` the manifest lists for this cell, through
    its own reader; a reader that finds nothing to read leaves it out."""
    out = {}
    for m in manifest[kind]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        spec = harness.load_json(_KIND_DIR[kind], m["name"] + ".json")
        reader = importlib.import_module(
            f"perfbench.readers.{spec['reader']}")
        value = reader.read(record, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(manifest, workload: str, record, traced: bool) -> str:
    device = dict(record["device"],
                  memory_peak_bytes=record["values"]["hbm_peak_bytes"])
    line = {"correct": bool(record["correct"]),
            "attempted": int(record["attempted"]),
            "failed": int(record["failed"]),
            "metrics": read_metrics(
                manifest, "per_layer" if traced else "end_to_end", workload,
                record),
            "device": device}
    if traced:
        s = trace_reduce.summary(record["trace"])
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
        line["breakdown"] = {"device_ops": s["device_ops"],
                             "idle_gaps": s["idle_gaps"]}
    return json.dumps(line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perfbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"perfbench: no workload {args.workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[args.workload]
    conf_entry = next(c for c in manifest["configs"]
                      if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf_entry["file"])) as f:
        conf = json.load(f)
    ctx = {"workload": cell["name"], "chips": cell["chips"], "config": conf,
           "traffic": harness.load_json("traffic", cell["traffic"] + ".json"),
           "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
           "sweep": [float(r) for r in args.sweep.split(",") if r],
           "on_chip": True, "t_start": T_START}
    runner = importlib.import_module(f"perfbench.runners.{conf['runner']}")
    record = runner.run(ctx)
    if record.get("sweep"):
        return 0
    print(result_line(manifest, cell["name"], record, bool(args.trace)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
