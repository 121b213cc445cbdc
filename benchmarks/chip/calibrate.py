"""Take the readings that set the limits of ``correct`` for one cell, in
one process on the chip:

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 12 \
        --controls 3 [--first-seed N] [--rehearse]

For each of ``--seeds`` seeds the program drives the cell's first steps and
the reference follows them (sound runs: the lower readings). For the first
``--controls`` of them the reference also runs as the control, in
bfloat16 in the program's place, and with the planted fault of half of
each batch left out (the upper readings). A step that leaves the state
unchanged reads 1 on both change gaps by their definition and needs no
run. One JSON line per reading goes to standard output; the last line
gives, per number, the lower and upper readings and the limit that
``checks/<cell>.json`` would take from them: above the lower reading and
below the upper, at lower**0.35 * upper**0.65, so that more of the room
lies above the lower reading.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

UNCHANGED = {"first_update_gap": 1.0, "change_gap": 1.0}


def limits(sound, uppers):
    """Per number: the lower reading (the largest sound one) and the upper
    one: the least of the sources' smallest readings, counting the control
    where its smallest reads 3x the lower or more and a fault where its
    smallest reads 10x or more (a state left unchanged: 3x)."""
    out = {}
    for name in sound[0]:
        lower = max(s[name] for s in sound)
        cands = []
        for kind, rows in uppers.items():
            vals = [r[name] for r in rows]
            if not vals or not all(math.isfinite(v) for v in vals):
                continue
            least = min(vals)
            factor = 3.0 if kind in ("control", "unchanged") else 10.0
            if least >= factor * max(lower, 1e-30):
                cands.append(least)
        upper = min(cands) if cands else None
        if name == "unmatched_rows":
            limit = 0.0
        elif upper is None:
            limit = None
        else:
            limit = lower ** 0.35 * upper ** 0.65
        out[name] = {"lower": lower, "upper": upper, "limit": limit}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 7)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench.harness import Run
    from chipbench import check
    sound, uppers = [], {"control": [], "half_batch": [],
                         "unchanged": [UNCHANGED]}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        run = Run(args.workload, seed, args.rehearse)
        run.setup()
        run.first_steps()
        run.free_program()
        ref = run.reference()
        row = run.numbers(ref)
        sound.append(row)
        print(json.dumps({"seed": seed, "kind": "program", **row}),
              flush=True)
        if i < args.controls:
            import jax.numpy as jnp
            for kind, kw in (("control", dict(dtype=jnp.bfloat16,
                                              precision="default")),
                             ("half_batch", dict(half_batch=True))):
                side = run.reference(**kw)
                row = check.numbers(side, ref, run.sizes, 0)
                uppers[kind].append(row)
                print(json.dumps({"seed": seed, "kind": kind, **row}),
                      flush=True)
        del run
    uppers["unchanged"] = [dict(sound[0], **UNCHANGED,
                                loss_gap=float("nan"),
                                unmatched_rows=float("nan"))]
    print(json.dumps({"workload": args.workload,
                      "limits": limits(sound, uppers)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
