"""Run one benchmark cell on the accelerator this process finds.

From the root of a checkout:

    python3 bench/run.py --workload products-sage.rudder --seed 7 \\
        --seconds 30 --trace 0

The last line of standard output is the result as one JSON object; the
numbers the correctness check compared, each with its limit, are also
the last lines of standard error. Without a TPU, with fewer chips than
the cell asks for, or outside a checkout of the repository, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: {ROOT} holds no program (src/repro)", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    # One fixed directory in the checkout, so that every run after the
    # first finds its programs; JAX reads JAX_COMPILATION_CACHE_DIR itself.
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import harness

    cell = harness.resolve_cell(ROOT, harness.load_spec(ROOT), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform})", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(
            f"bench: {args.workload} needs {cell.chips} chips, JAX sees {len(devices)}",
            file=sys.stderr,
        )
        return 1
    out = harness.run_cell(
        ROOT, args.workload, args.seed, args.seconds, bool(args.trace), T_START
    )
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
