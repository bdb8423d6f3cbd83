"""On-chip benchmark of flame-repro: one cell per run, one JSON line out.

From the root of a checkout, on a machine with the chips the cell asks for:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json``. With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the window
runs under the JAX profiler and the result carries its per-layer metrics,
the device's busy time and the breakdown. Both check the timed path against
the plain reference (``correct``) and print each compared number beside its
limit, last on standard error and last in the result line.

A run that finds no TPU, or fewer chips than the cell asks for, exits with
status 2 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def use_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is cached,
    however quickly it compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.harness import run_workload
    from chipbench.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    use_cache()
    result = run_workload(manifest, args.workload, args.seed % (1 << 64),
                          args.seconds, bool(args.trace), devices, T0)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
