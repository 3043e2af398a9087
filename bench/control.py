"""The readings a cell's correctness limits are set from, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <a,b,...> \\
        [--control-seeds <n>]

For every seed it makes one full run of the cell (the timed path, then
the comparison with the reference) and prints the numbers compared, with
the quantiles of the served tokens' gaps.  For the first
``--control-seeds`` seeds it also reads the control on the same sampled
requests: the reference computed one precision below the configuration's
(int4 activation codes where the configuration states int8), put in the
program's place — at each served position, the gap below the reference's
best logit of the token the control puts first.  The limit lies between
the program's largest reading and the control's smallest.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH.parent), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def control_bits(cfg_json: dict) -> int:
    """int8 -> int4, int4 -> int2: the nearest precision below."""
    from bench.references import dense_gqa
    return dense_gqa.act_bits(cfg_json["model"]["precision"]) // 2


def _done(keep):
    return [r for r in keep["picked"] if r.done]


def spread(gap) -> dict:
    """Quantiles of the per-token gaps."""
    q = np.quantile(gap, [0.5, 0.9, 0.99]) if gap.size else [np.nan] * 3
    return {"gap_p50": float(q[0]), "gap_p90": float(q[1]),
            "gap_p99": float(q[2])}


def control_gaps(keep, seed):
    """The gaps of the tokens the control puts first, at the same
    positions of the same prompts and served tokens."""
    from bench import check
    cfg, done = keep["config"], _done(keep)
    ctl = check.reference(cfg, seed, done, a_bits=control_bits(cfg))
    return check.reference(cfg, seed, done, ids=ctl["argmax"])["gap"]


def control_numbers(keep, seed) -> dict:
    from bench import check
    return check.numbers(control_gaps(keep, seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    a = ap.parse_args(argv)
    from bench import check, run
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        keep = {}
        res = run.run_cell(a.workload, seed, a.seconds, False, keep=keep)
        line = {"seed": seed, "correct": res["correct"],
                "program": {k: v["value"] for k, v in res["checked"].items()},
                "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        line["program"].update(spread(keep["gap"]))
        if i < a.control_seeds:
            g = control_gaps(keep, seed)
            line["control"] = {**check.numbers(g), **spread(g)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
