#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload susy.fit --seed 7 --seconds 10 --trace 0

The cell is one entry of ``workloads`` in ``BENCHMARK.json`` (see
``bench/cell.py`` for what a run does). The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with the plain reference, beside its
limit. The checks are also the last lines of standard error.

The run fails, printing no result, without a TPU, with fewer chips than the
cell asks for, or without this checkout's ``src/repro``. JAX's persistent
compilation cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(msg, flush=True)


def prepare(workload: str):
    """Check the checkout and the chips and set up JAX; returns the cell."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no system under test at {src}/repro")
    for k in sorted(k for k in os.environ if k.startswith("REPRO_")):
        log(f"bench: ignoring {k}={os.environ.pop(k)!r}")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, src)

    import jax

    import cell as cells

    spec = cells.load_json(ROOT, "BENCHMARK.json")
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}.get(workload)
    cell = cells.find_cell(ROOT, workload)
    if jax.default_backend() != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform {jax.default_backend()!r})")
    if len(jax.devices()) < chips:
        raise SystemExit(f"bench: {workload} needs {chips} chips, "
                         f"JAX sees {len(jax.devices())}")
    import repro
    from repro.runtime.compile_cache import enable_compile_cache

    if not os.path.abspath(repro.__file__).startswith(src + os.sep):
        raise SystemExit(f"bench: repro imported from {repro.__file__}, not {src}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {enable_compile_cache()}")
    return cell


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also copy the traced window's xplane file into DIR")
    args = ap.parse_args()
    if not 0 <= args.seed < 2 ** 32:
        raise SystemExit(f"--seed must lie in [0, 2**32), got {args.seed}")
    cell = prepare(args.workload)
    import cell as cells

    counter = cells.CompileCounter()
    result = cells.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            t_start=T_START, counter=counter,
                            keep_trace=args.keep_trace, log=log)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
