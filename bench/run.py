#!/usr/bin/env python3
"""Run one cell of the benchmark once, on a TPU.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are found by name
through ``BENCHMARK.json`` (see ``harness.py``).  Progress goes to
standard error as JSON lines; the compared numbers, each beside its
limit, are the last lines there.  The last line of standard output is
the result object.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits 1.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def log(**kw) -> None:
    print(json.dumps(kw, default=str), file=sys.stderr, flush=True)


def devices(chips: int):
    """The accelerator, or an error naming what JAX found instead."""
    import jax

    from harness import BenchError

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if devs[0].device_kind not in peaks:
        raise BenchError(f"device kind {devs[0].device_kind!r} is not in "
                         f"peaks.json")
    return devs


def compile_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path in
    the checkout (``repro.chip.CACHE_DIR``, or ``JAX_COMPILATION_CACHE_DIR``
    when set), holding every program however fast it compiled."""
    import jax

    from repro.chip import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from harness import BenchError, load_cell, run_cell

    try:
        import repro  # noqa: F401  (the system under test, from src/)
    except ImportError as e:
        print(f"bench: cannot import the program from "
              f"{BENCH.parent / 'src'}: {e}", file=sys.stderr)
        return 1
    try:
        cell = load_cell(args.workload)
        devs = devices(cell.chips)
        log(phase="start", workload=cell.name, seed=args.seed,
            platform=devs[0].platform, kind=devs[0].device_kind,
            devices=len(devs), compile_cache=compile_cache())
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START, log=log)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in out["compared"].items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
