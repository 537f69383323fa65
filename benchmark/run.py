#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, one configuration or one per-layer metric
is found by its name in BENCHMARK.json (see benchmark/README.md); this file
holds none of those names. The last line of standard output is the result; on
any platform but the TPU there is none and the exit code is not 0.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.harness import spec as specmod  # noqa: E402
from benchmark.harness.tracing import Tracer, registry_counters  # noqa: E402

EXIT_NO_DEVICE = 2
EXIT_REHEARSAL = 3


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = specmod.Spec()
    cell = spec.cell(args.workload)

    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not rehearsal:
        log(f"benchmark: no accelerator (JAX reports {device}); set "
            f"JAX_PLATFORMS=cpu for a rehearsal, which prints no result")
        return EXIT_NO_DEVICE
    if device["platform"] == "tpu" and device["count"] < int(cell["chips"]):
        log(f"benchmark: cell needs {cell['chips']} chips, JAX reports {device}")
        return EXIT_NO_DEVICE

    result, lines = measure(spec, cell, args.seed, args.seconds,
                            bool(args.trace), device, rehearsal)
    for line in lines:
        log(line)
    if device["platform"] != "tpu":
        log(f"benchmark: the whole path ran on platform {device['platform']!r}"
            f" (correct={result['correct']}); that is a rehearsal, not a chip "
            f"run: no result line")
        return EXIT_REHEARSAL
    print(json.dumps(result), flush=True)
    return 0


def measure(spec, cell: dict, seed: int, seconds: float, trace: bool,
            device: dict, rehearsal: bool, driver_hook=None):
    """One run of one cell after the look for a chip: set-up, the window, the
    comparison with the reference, the metrics. Returns the result (what the
    last line holds) and the lines that go before it on standard error.
    ``driver_hook(driver)`` lets the tests under benchmark/tests break the
    timed path, or put the control in its place, before set-up."""
    import jax

    workload = spec.workload_file(cell["name"])
    config = spec.config(cell["config"])
    devs = jax.devices()
    # the program's one compile cache (JAX_COMPILATION_CACHE_DIR, or the fixed
    # path in the checkout); keep every program, however quickly it compiled
    from reth_tpu.ops.device import configure_compile_cache

    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    tracer = Tracer(enabled=trace, out_dir=specmod.ROOT / ".bench_trace")

    driver = specmod.load_driver(workload["driver"]).Driver(
        config, workload, seed, rehearsal)
    if driver_hook is not None:
        driver_hook(driver)
    driver.setup()
    setup_s = time.time() - T_PROCESS_START
    set_up = tracer.compile_counts()
    counters0 = registry_counters()

    driver.window(seconds, tracer)

    in_window = {k: v - set_up[k] for k, v in tracer.compile_counts().items()}
    counters1 = registry_counters()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:int(cell["chips"])])
    cpu_routes = _moved_cpu_routes(counters0, counters1)
    driver.release()

    t_check = time.time()
    checks = list(driver.check())
    checks.append(("cpu_routes_moved", float(len(cpu_routes)), 0.0))
    check_s = time.time() - t_check
    correct = all(v <= lim for _, v, lim in checks)

    facts = driver.facts()
    facts.update(setup_s=setup_s, counters_before=counters0,
                 counters_after=counters1, memory_peak_bytes=peak,
                 device=device, trace=tracer.reduced())
    values = dict(driver.end_to_end(), setup_s=setup_s)
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics(group, cell["name"]):
        if group == "end_to_end":
            v = values.get(m["name"])
        else:
            mf = spec.metric_file(m["name"])
            v = specmod.load_reader(mf["reader"]).read(facts, mf.get("params", {}))
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    lines = [
        f"benchmark: cell {cell['name']} seed {seed} window "
        f"{facts.get('seconds', 0.0):.3f}s operations {facts.get('ops')} "
        f"(samples of each timing: {len(facts.get('op_seconds', []))}; seconds "
        f"{' '.join(f'{x:.2f}' for x in facts.get('op_seconds', []))}) "
        f"set-up {setup_s:.1f}s check {check_s:.1f}s cache {cache_dir}",
        f"benchmark: programs compiled in set-up {set_up['compiled']}, cache "
        f"hits {set_up['cache_hits']}, new shapes {set_up['shapes']}; inside "
        f"the window compiled {in_window['compiled']}, loaded from the cache "
        f"{in_window['cache_hits']}, new shapes {in_window['shapes']}"]
    if cpu_routes:
        lines.append(f"benchmark: CPU-route counters moved: {cpu_routes}")
    lines += [f"benchmark: failed operation: {e}" for e in driver.errors]
    lines += [f"benchmark: {note}" for note in facts.get("notes", [])]
    lines += [f"check {name} {v:g} limit {lim:g} {'ok' if v <= lim else 'FAIL'}"
              for name, v, lim in checks]

    dev_out = dict(device, memory_peak_bytes=int(peak))
    result = {"correct": bool(correct), "attempted": int(driver.attempted),
              "failed": int(driver.failed), "metrics": metrics,
              "device": dev_out}
    if trace:
        red = facts["trace"] or {}
        dev_out["busy_s"] = red.get("busy_s", 0.0)
        dev_out["window_s"] = red.get("window_s", 0.0)
        result["breakdown"] = {"device_ops": red.get("device_ops", []),
                               "idle_gaps": red.get("idle_gaps", [])}
    # programs built inside the window: compiled, or loaded from the cache
    result["compiled_in_window"] = in_window["programs"]
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result, lines


def _moved_cpu_routes(before: dict, after: dict) -> dict:
    """Counters that say work left the device (the program's own list)."""
    from reth_tpu.ops.device import CPU_ROUTE_COUNTERS

    return {k: after.get(k, 0.0) - before.get(k, 0.0)
            for k in CPU_ROUTE_COUNTERS
            if after.get(k, 0.0) != before.get(k, 0.0)}


if __name__ == "__main__":
    sys.exit(main())
