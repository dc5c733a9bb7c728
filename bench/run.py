"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Builds the cell's store from the seed,
warms it up, measures a closed loop for ``--seconds``, compares every
answer with the plain reference, and prints one JSON object as the last
line of standard output.  With ``--trace 1`` the window runs under the
JAX profiler and the line carries the per-layer metrics in place of the
end-to-end ones.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: JAX's persistent compilation cache, at a fixed path in the checkout
#: so that only a cell's first run there compiles
CACHE_DIR = ROOT / ".bench_run" / "jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # libtpu would log under /tmp: keep every write inside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, however quick to compile, so that set-up
    # after the first run does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import harness
    try:
        out = harness.run_cell(ROOT, args.workload,
                               args.seed % (1 << 63), args.seconds,
                               bool(args.trace), T_START)
    except harness.NoDevice as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
