#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: refuse without the chips the cell asks for (exit 3, no result);
keep JAX's compilation cache in ``<checkout>/.jax_cache``; build the cell
from its files (``bench/lib/registry.py``); set up and warm only the
cell's own shapes; measure for ``--seconds``; check the timed path's
output against the plain reference; print the numbers compared beside
their limits as the last lines of standard error, and one JSON object as
the last line of standard output.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, ".out", "trace")


class Context:
    """What a traffic runner needs from the harness for one run."""

    def __init__(self, *, mod, cfg, traffic, seed, seconds, trace, devices,
                 t_start):
        from bench.lib.trace import Spans
        self.mod, self.cfg, self.traffic = mod, cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.t_start = devices, t_start
        self.span = Spans(trace)

    def capture(self):
        from bench.lib import trace
        return (trace.capture(TRACE_DIR) if self.trace
                else contextlib.nullcontext())

    def read_memory(self):
        from bench.lib import device
        return device.memory_peak_bytes(self.devices)

    @staticmethod
    def note(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


class Readings:
    """What a per-layer metric's ``read`` gets: the reduced trace, the
    run's facts, the device's peaks and the kernels' event names."""

    def __init__(self, trace, facts, peaks, kernels, cfg, traffic, chips):
        from bench.lib import trace as tr
        self.trace, self.facts, self.peaks = trace, facts, peaks
        self.kernels, self.cfg, self.traffic = kernels, cfg, traffic
        self.chips = chips
        self.time = tr.device_time(trace)
        self.window_s = self.time["window_s"]


def finite(x):
    return x if x is not None and math.isfinite(x) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # a fixed directory inside this checkout, whatever the environment
    # says, so that only the first run here compiles and no other
    # checkout shares it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib import device, registry

    bench = registry.benchmark(ROOT)
    cell = registry.cell(bench, args.workload)
    devices = device.require(cell["chips"])

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg, mod = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    limits = registry.limits(cell["name"])
    e2e, layer = registry.cell_metrics(bench, cell["name"])
    ctx = Context(mod=mod, cfg=cfg, traffic=traffic, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  devices=devices, t_start=T_START)
    out = registry.runner(traffic["kind"]).run(ctx)

    from bench.lib import checks
    correct, shown = checks.judge(out["numbers"], limits["limits"])
    correct = correct and out["failed"] == 0
    info = device.info(devices)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": info}
    if args.trace:
        from bench.lib import trace as tr
        t = tr.load(TRACE_DIR)
        r = Readings(t, out["facts"], device.peaks(info["kind"]),
                     registry.kernels(), cfg, traffic, cell["chips"])
        for name, busy in r.time["per_device_busy_s"].items():
            ctx.note(f"device {name} busy_s={busy} "
                     f"window_s={r.window_s}")
        for m in layer:
            v = finite(registry.metric_reader(m["name"])(r))
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        info.update(busy_s=r.time["busy_s"], window_s=r.window_s)
        result["breakdown"] = {"device_ops": tr.top_ops(t),
                               "idle_gaps": tr.top_gaps(t)}
    else:
        values = dict(out["end_to_end"], setup_s=out["setup_s"])
        for m in e2e:
            v = finite(values.get(m["name"]))
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
    result["check"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                       for k, v in shown.items()}
    for k, v in shown.items():
        ctx.note(f"check {k}={v['value']!r} limit={v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit as e:
        msg = getattr(e, "msg", None)
        if msg:
            print(msg, file=sys.stderr, flush=True)
        raise
    except Exception:  # noqa: BLE001 -- report, print no result, fail
        traceback.print_exc()
        sys.exit(1)
