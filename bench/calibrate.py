"""Readings that a cell's correctness limit is set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3

For each seed, in one process on the cell's chips: the timed path at the
cell's own size (one batch, holding its longest request), then the
comparison the benchmark's run makes (the program's reading), then the
control: the reference in the place of the program, its matrix products
in float8 e4m3 (the precision below the configuration's bf16), read by
the same number: at every position of the same prompts and served tokens,
the token the control ranks first, and its gap to the float32
reference's best logit.

The limit lies above the largest program reading over a dozen seeds or
more and below the smallest control reading.  Prints one JSON line per
seed and a summary line; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as R  # noqa: E402


def serve_readings(ctx, drv):
    import jax.numpy as jnp
    import numpy as np

    from bench.lib import compare

    state = drv.setup(ctx, warm=False)
    rec = drv.window(ctx, state)
    drv.release(state)
    del state
    gc.collect()
    ref, ctl, served = drv.reference_logits(ctx, rec, quant="fp8")
    program = float(compare.token_gaps(ref, served).max())
    first = np.where(served >= 0, compare.argmax_tokens(ctl), -1)
    control = float(compare.token_gaps(ref, first).max())
    return {"program": program, "control": control, "tokens": int((served >= 0).sum()),
            "control_agrees": float(np.mean((first == served)[served >= 0])),
            "logit_std": float(jnp.std(ref))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    ctx = R.open_cell(R.find_cell(args.workload), 0, 0.0, False)
    drv = R.load_module("drivers", ctx.cell.traffic["driver"])
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx.seed = seed
        row = {"seed": seed, **serve_readings(ctx, drv)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": ctx.cell.name, "lower": max(r["program"] for r in rows),
                      "upper": min(r["control"] for r in rows), "seeds": len(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
