#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from, many seeds in one process.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \\
        --controls int8 fp8

For each seed: one run of the cell as ``bench/run.py`` makes it (a window
at the cell's own load, then the comparison with the plain reference),
plus the control reading of each compared number: the reference computed
with its weights (a served model) or its K/V (the model-free K/V path)
rounded to each precision below the configuration's. One JSON line per
seed; the program's numbers are the lower readings of a limit, the
controls' the upper ones. The benchmark's own runs never run the control.
Needs a TPU, like ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=["int8"])
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("control: first JAX device is not a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in args.seeds:
        got: dict = {}
        t0 = time.perf_counter()
        out = harness.run(args.workload, seed, args.seconds, False,
                          controls=tuple(args.controls),
                          log=lambda **kw: got.update(kw))
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"], **got,
                          "wall_s": time.perf_counter() - t0}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
