"""The benchmark's command: one process, one cell, one run.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout. Everything a cell is made of is found
by name from ``BENCHMARK.json``: its configuration's file, its traffic
mix ``traffic/<mix>.json``, the job driver ``jobs/<job>.py`` the mix
names, the model family ``families/<family>.py`` the configuration
names, and one reader ``layer_metrics/<metric>.py`` per per-layer
metric. The last line of standard output is the result; earlier lines
are JSON rows of what was checked.

Without a TPU, or with fewer chips than the cell asks for, a cell of
``BENCHMARK.json`` exits non-zero and prints no result. ``main(argv,
manifest=<path>)`` — a Python argument, for the rehearsals in
``tests/chipbench`` — runs the cells of another manifest on whatever
backend there is; off a TPU its line carries counts only, never a time,
a rate or a share.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python can say

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
COUNT_SOURCES = ("program_counter",)  # what a run off the chip may report


class Manifest:
    """``BENCHMARK.json`` (or a rehearsal's manifest) and the files its
    names resolve to, looked up under each of its ``paths``."""

    def __init__(self, path: str):
        with open(path) as f:
            self.doc = json.load(f)
        self.base = os.path.dirname(os.path.abspath(path))
        self.dirs = [os.path.join(self.base, p) for p in self.doc["paths"]]

    def find(self, *parts: str) -> str:
        for d in self.dirs + [HERE]:
            path = os.path.join(d, *parts)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"{os.path.join(*parts)} under none of {self.doc['paths']}")

    def cell(self, name: str) -> dict:
        cells = {c["name"]: c for c in self.doc["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
        return cells[name]

    def config(self, name: str) -> dict:
        entry = next(c for c in self.doc["configs"] if c["name"] == name)
        with open(os.path.join(self.base, entry["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        with open(self.find("traffic", f"{name}.json")) as f:
            return json.load(f)

    def metrics(self, group: str, cell: str) -> list[dict]:
        return [m for m in self.doc[group]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        path = self.find("layer_metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            "chipbench_layer_metric_" + metric.replace(".", "_"), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


def main(argv=None, manifest: str | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    rehearsal = manifest is not None
    man = Manifest(manifest or os.path.join(ROOT, "BENCHMARK.json"))
    cell = man.cell(args.workload)
    scratch = os.path.join(ROOT, ".chipbench_run", cell["name"])
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)

    def claim_devices(devices):
        """The cell's chips, or no run: a cell of BENCHMARK.json needs a
        TPU, and every cell needs as many devices as it asks for."""
        if devices[0].platform != "tpu" and not rehearsal:
            raise SystemExit(f"chipbench: JAX initialised platform "
                             f"{devices[0].platform!r}; {cell['name']} "
                             "needs 'tpu'")
        if len(devices) < cell["chips"]:
            raise SystemExit(f"chipbench: {cell['name']} needs "
                             f"{cell['chips']} devices, JAX found "
                             f"{len(devices)}")
        return list(devices[:cell["chips"]])

    ctx = types.SimpleNamespace(
        t0=T0, root=ROOT, scratch=scratch, cell=cell, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), rehearsal=rehearsal,
        config=man.config(cell["config"]), traffic=man.traffic(cell["traffic"]),
        claim_devices=claim_devices)
    job = importlib.import_module(f"chipbench.jobs.{ctx.traffic['job']}")
    result = job.run(ctx)

    on_chip = result["device"]["platform"] == "tpu"
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {},
            "device": result["device"]}
    if args.trace:
        from chipbench import trace_reduce

        trace = summary = None
        if result.get("trace_dir"):
            trace = trace_reduce.load(result["trace_dir"])
            summary = trace and trace_reduce.summarize(trace)
        if summary:
            line["device"]["busy_s"] = summary["busy_s"]
            line["device"]["window_s"] = summary["window_s"]
            line["breakdown"] = {
                "device_ops": trace_reduce.top_ops(summary),
                "idle_gaps": summary["gaps"]}
        reported = {m["name"] for m in man.metrics("end_to_end", cell["name"])}
        for m in man.metrics("per_layer", cell["name"]):
            if m["moves"] not in reported:
                continue
            if not on_chip and m["source"] not in COUNT_SOURCES:
                continue
            value = man.reader(m["name"])(
                summary, result["spans"], result["counters"],
                dict(cell, config=ctx.config, traffic=ctx.traffic,
                     shape=result["shape"], peaks=result["peaks"]))
            if value is not None:
                line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    elif on_chip:
        for m in man.metrics("end_to_end", cell["name"]):
            line["metrics"][m["name"]] = {
                "value": result["end_to_end"][m["name"]], "unit": m["unit"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
