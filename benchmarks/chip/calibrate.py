"""Readings that set a cell's limits: the program's numbers over many seeds,
and the control's over a few.

From the root of a checkout, on a machine with the chips the cell asks for:

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 101,102,... --faulted 3 [--seconds 2]

For each seed it runs the cell's timed path as a run does (set-up and a
short window of rounds) and compares it with the plain reference; for the
first ``--faulted`` seeds it also compares the control, the reference's
fold in bfloat16, with the reference. One JSON line per seed goes to
standard output and to ``chiprun_out/calibrate-<name>.jsonl``.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def tag_readings(cell, devices, seeds, faulted, seconds):
    from chipbench.cells import tag_round as tg

    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        r = tg.make_rounds(cell, seed, seconds)
        tg.run_rounds(r, cell.config, cell.traffic)
        line = {"seed": seed, "rounds": len(r.agg_s), "compared": len(r.kept),
                "program": {"mismatched": tg.mismatched(r, tg.reference(r))}}
        if i < faulted:
            line["control"] = {"mismatched": tg.mismatched(r, tg.reference(r, True))}
        line["seconds"] = time.perf_counter() - t
        yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--faulted", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import jax

    from chipbench.manifest import Manifest

    cell = Manifest(ROOT).cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: needs {cell.chips} TPU chips, found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    devices = devices[: cell.chips]
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    seeds = [int(s) for s in args.seeds.split(",")]
    lines = tag_readings(cell, devices, seeds, args.faulted, args.seconds)
    out = ROOT / "chiprun_out" / f"calibrate-{args.workload}.jsonl"
    out.parent.mkdir(exist_ok=True)
    with out.open("w") as f:
        for line in lines:
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
