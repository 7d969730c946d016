"""`python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`: one run of one cell in this process, on the machine it is
started on. Fails, printing no result, where JAX finds no accelerator or
fewer chips than the cell asks for. `--rehearse` walks the same command at
tiny widths on the CPU and never prints `correct: true`."""
import time

_T_PROC = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=None,
                    help="read BENCHMARK.json and benchmark/ data from here")
    ap.add_argument("--control", default="",
                    help="also read the control (e.g. fp8) on the sample")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import harness
    return harness.run_cell(args, _T_PROC)


if __name__ == "__main__":
    sys.exit(main())
