"""Entry point: one cell, once, in this process.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

run from the repository's root.  Loads the cell's files by name, hands
them to the runner its traffic file names, and prints the contract's
result line last.  Without a TPU it exits non-zero and prints no result
line; ``--rehearse`` instead runs the files' ``rehearse`` sizes on the
CPU with Pallas in interpret mode, to debug the benchmark itself: its
last line names the CPU as the device and its numbers are not device
numbers.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()        # set-up runs from here

import argparse                  # noqa: E402
import os                        # noqa: E402
import sys                       # noqa: E402
import traceback                 # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the measured window (default: BENCHMARK.json's "
                         "run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' tiny sizes")
    ap.add_argument("--sweep", default=None,
                    help="open-loop cells: comma-separated rates to offer "
                         "for 20 s each after one set-up; prints a table "
                         "and no result line")
    ap.add_argument("--out", default=None,
                    help="directory for traces and event logs (default: "
                         "benchmark_out/<cell> in the checkout)")
    args = ap.parse_args(argv)
    if args.rehearse:
        # before jax or the program is imported
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FLAGS_pallas_interpret"] = "1"

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness
    if args.seconds is None:
        args.seconds = float(harness.load_manifest()["run_seconds"])
    if args.out is None:
        args.out = os.path.join(root, "benchmark_out", args.workload)
    args.out = os.path.abspath(args.out)
    os.makedirs(args.out, exist_ok=True)
    try:
        cell = harness.load_cell(args.workload, args.rehearse)
        # the program places jax's persistent compile cache in the
        # checkout (paddle_tpu/__init__.py: JAX_COMPILATION_CACHE_DIR if
        # set, else <checkout>/.jax_cache); the benchmark only asks that
        # every program be kept, however quickly it compiled, so that a
        # warm run's set-up compiles nothing
        import jax
        import paddle_tpu  # noqa: F401
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        runner = harness.resolve(
            f"runners.{cell['traffic']['runner']}:run")
        line = runner(cell, args, harness.SetupClock(_T0))
    except harness.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 1
    except Exception:  # noqa: BLE001 — the boundary: report, fail, no line
        traceback.print_exc()
        return 1
    if line:
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # engine and profiler threads are daemons; leave without waiting on
    # a device runtime's teardown
    os._exit(code)
