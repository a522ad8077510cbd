"""The control of the check: the reference with bfloat16 cycle sums in
the program's place, compared as a run compares the program.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --dispatches <n>

For each seed it draws the lanes a run of ``--dispatches`` window
dispatches would sample, runs them through the float32 reference and
through the bfloat16 one, and prints how many of them differ: the
check's ``lanes_mismatched`` as the control reads it.  ``--dispatches``
is what a window of ``run_seconds`` makes for the cell (at 20 s on one
v5e: about 10 for ``native28.all11`` and 34 for ``virt5.all11``).  Runs
on the host CPU; it needs no chip.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cell as cellmod  # noqa: E402
from bench import check, spec  # noqa: E402


def control_lanes(cfg_file: dict, traffic: dict, seed: int,
                  dispatches: int) -> dict:
    """Mismatched lanes of the bfloat16 reference against the float32
    one, over the sample a run of ``dispatches`` would check."""
    members = list(cfg_file["members"])
    c = cellmod.Cell(cfg_file, traffic, seed, 1, "")
    per = int(cfg_file["check_per_member"])
    picks = check.sample(len(members), dispatches, c.width, per, seed,
                         c.cores)
    # the window's dispatch k is the cell's k + 1 (0 warms the shape)
    jobs = [(s, check.column(c.segments(k + 1), *lane))
            for s, k, *lane in picks]
    want = check.Reference(cfg_file, members).lanes(jobs)
    got = check.Reference(cfg_file, members, acc="bfloat16").lanes(jobs)
    bad = [check.diff(g, w) for (g, _), (w, _) in zip(got, want)]
    return {"seed": seed, "lanes_checked": len(jobs),
            "lanes_mismatched": sum(bool(d) for d in bad),
            "fields": sorted({f for d in bad for f in d})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dispatches", type=int, required=True)
    args = ap.parse_args(argv)
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    cfg_file = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    for seed in args.seeds:
        print(json.dumps(control_lanes(cfg_file, traffic, seed,
                                       args.dispatches)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
