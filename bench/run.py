#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is an entry of
``BENCHMARK.json``'s ``workloads``. With ``--trace 0`` the last line of
standard output is a JSON object with the cell's end-to-end metrics; with
``--trace 1`` it carries the per-layer metrics read from a profiler trace
of the first part of the window, the device's busy time and a breakdown.
Every run ends by comparing what the window served with the plain
reference: each compared number and its limit are the last lines of
standard error and the ``checks`` entry of the result.

Without a TPU, or with fewer chips than the cell asks for, it prints
what JAX found and exits with code 3; nothing falls back to the CPU.

The line's ``path`` says which of the engine's paths the window timed:
whether its fused iteration program held, and the error that made it
fall back to the per-call programs where it did not.

``--readings`` also prints the control's and a corrupted stream's numbers
(used to set the limits; the benchmark's own runs leave it off).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    if set, else ``<checkout>/.jax_cache``; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--readings", action="store_true")
    ap.add_argument("--rate", type=float, default=None,
                    help="open loop: override the cell's arrival rate (the "
                         "sweep that finds the knee)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no system under test at {ROOT / 'src' / 'repro'}; "
              f"run from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    if args.rate is not None:
        cell.shape["rate_rps"] = args.rate
    cache = enable_cache()
    import jax

    devices = jax.devices()
    found = f"{devices[0].platform} ({devices[0].device_kind}) x{len(devices)}"
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {found}; nothing was run",
              file=sys.stderr)
        return 3
    chips = int(cell.entry["chips"])
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX found "
              f"{found}", file=sys.stderr)
        return 3
    print(f"bench: {args.workload} seed {args.seed} on {found}; compile "
          f"cache {cache}", file=sys.stderr, flush=True)

    trace_dir = None
    if args.trace:
        trace_dir = str(ROOT / ".bench_trace" / f"{args.workload}-{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        res = harness.run(cell, args.seed, args.seconds, trace_dir, T_START,
                          readings=args.readings)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": res.memory_peak_bytes}
    if args.trace:
        device["busy_s"] = res.busy_s
        device["window_s"] = res.window_s
    line = {"correct": res.correct, "attempted": res.attempted,
            "failed": res.failed, "metrics": res.metrics, "device": device}
    if res.breakdown is not None:
        line["breakdown"] = res.breakdown
    line["path"] = res.path
    line["notes"] = res.notes
    line["checks"] = res.checks
    for name, c in res.checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
