"""Serving benchmark: one cell of ``BENCHMARK.json`` through ``Engine`` on
the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and ``checks`` last);
the last lines of standard error give each compared number beside its
limit.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiler trace of a few seconds in the
middle of the window.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE = ROOT / ".jax_cache"


def _setup_paths() -> None:
    """The benchmark imports as ``bench``, the program from ``src``.  JAX
    keeps its persistent compilation cache at a fixed path inside the
    checkout, so only a checkout's first run of a cell compiles."""
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _setup_paths()
    try:
        import jax

        jax.config.update("jax_compilation_cache_dir", str(CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        import repro  # noqa: F401  (the system under test must be there)
        from bench import harness
    except ImportError as e:
        print(f"bench: cannot import the benchmark or the program: {e}",
              file=sys.stderr)
        return 2
    try:
        cell = harness.load_cell(args.workload)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    checks = result["checks"]
    for k, v in checks.items():
        print(f"{k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
