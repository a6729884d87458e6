#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the TPU chips the cell asks
for. The cell is an entry of ``BENCHMARK.json``; everything it needs is
read from the files that entry names. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, last, ``checks``: each number compared with the plain
reference beside its limit (``max``), and each count of what was compared
beside its least (``min``). The same numbers close standard error.

Exits non-zero with no result line when the first JAX device is not a TPU,
when its kind is not in ``bench/peaks.json``, or when there are fewer
devices than the cell asks for. The persistent compilation cache is
``.jax_cache`` in the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    spec = harness.load_spec(args.workload)
    chips = int(spec["workload"]["chips"])

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"bench: first JAX device is {dev.platform!r}, not a TPU", file=sys.stderr)
        return 2
    try:
        harness.peak_of(dev.device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                      spec=spec, t_start=T_START)
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
