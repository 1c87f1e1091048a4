"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic, limits and metrics are found from
``BENCHMARK.json`` at the root of the checkout (see ``harness``).  Without
a TPU, with fewer chips than the cell asks for, or without the program's
``src/`` beside it, it exits non-zero and prints no result.  Otherwise the
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number that decides ``correct``
beside its limit.  The same numbers end standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Context:
    """What a driver is given, and what it hands back to the harness while
    the program is still live (``after_window``)."""

    def __init__(self, bench, cell, seed, seconds, trace, devices, *,
                 t_start, smoke=False, traffic=None, limits=None):
        self.bench, self.cell = bench, cell
        self.config = bench.config(cell["config"])
        self.traffic = traffic or bench.traffic(cell["traffic"])
        self.limits = limits or bench.limits(cell["name"])
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.devices = devices
        self.t_start = t_start
        self.smoke = smoke
        self.readings = {}

    @staticmethod
    def say(msg):
        print(f"chipbench: {msg}", file=sys.stderr, flush=True)

    def start_trace(self):
        import jax
        os.makedirs(TRACE_DIR, exist_ok=True)
        for f in _files(TRACE_DIR):
            os.remove(f)
        jax.profiler.start_trace(TRACE_DIR)

    def stop_trace(self):
        import jax
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        self.say(f"trace stopped: {time.monotonic() - t0:.1f} s")

    def after_window(self, out):
        """Read device memory, and with tracing the per-layer metrics,
        while the program's state is still on the chips."""
        from chipbench import trace
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)
        self.readings["memory_peak_bytes"] = peak
        out["metrics"]["peak_hbm_gib"] = peak / 2 ** 30
        if not self.trace:
            return
        path = [f for f in _files(TRACE_DIR) if f.endswith(".xplane.pb")]
        if len(path) != 1:
            raise RuntimeError(f"expected one trace under {TRACE_DIR}, "
                               f"found {path}")
        t0 = time.monotonic()
        summary = trace.reduce_file(path[0], len(self.devices))
        self.say(f"trace read: {time.monotonic() - t0:.1f} s")
        self.readings["busy_s"] = summary.busy_s
        self.readings["window_s"] = summary.window_s
        self.readings["breakdown"] = summary.breakdown()
        info = MetricInput(self, summary)
        values = {}
        for m in self.bench.metrics(self.cell["name"], trace=True):
            v = self.bench.reader(m["name"]).read(info)
            if v is not None:
                values[m["name"]] = v
        out["layer_metrics"] = values


class MetricInput:
    """What a per-layer reader may read: the reduced trace, the cell's
    model and traffic, the chips' peaks and the steps the window ran."""

    def __init__(self, ctx, summary):
        from chipbench import flops
        self.trace = summary
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.chips = len(ctx.devices)
        self.peaks = flops.peaks(ctx.devices[0].device_kind)
        from chipbench.reference import Model
        self.model = Model.from_config(ctx.config)
        self.steps = summary.steps


def _files(d):
    out = []
    for base, _, names in os.walk(d):
        out += [os.path.join(base, n) for n in names]
    return out


def run_cell(bench, name, seed, seconds, trace, devices, *, t_start,
             smoke=False, traffic=None, limits=None):
    """Run cell ``name`` on ``devices``; returns the result object."""
    cell = bench.cell(name)
    ctx = Context(bench, cell, seed, seconds, trace, devices,
                  t_start=t_start, smoke=smoke, traffic=traffic,
                  limits=limits)
    kind = ctx.traffic["kind"]
    out = bench.driver(kind).run(ctx)
    from chipbench import compare
    checks = compare.checks(out["numbers"], ctx.limits)
    correct = (all(c["ok"] for c in checks.values()) and out["failed"] == 0
               and all(math.isfinite(v) for v in out["numbers"].values()))
    wanted = bench.metrics(name, trace=bool(trace))
    source = out["layer_metrics"] if trace else out["metrics"]
    metrics = {m["name"]: {"value": source[m["name"]], "unit": m["unit"]}
               for m in wanted if m["name"] in source}
    missing = [m["name"] for m in wanted if m["name"] not in source]
    if missing and not trace:
        raise RuntimeError(f"driver gave no {missing}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.readings["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = ctx.readings["busy_s"]
        device["window_s"] = ctx.readings["window_s"]
        result["breakdown"] = ctx.readings["breakdown"]
    result["window_compiles"] = out["window_compiles"]
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def main(argv=None):
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no program under {ROOT}/src", file=sys.stderr)
        return 2
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench.harness import Benchmark
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"chipbench: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      args.trace, devices[:cell["chips"]], t_start=T_START)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
