"""Readings for the limit of the ``correct`` comparison, in one process.

    python3 chipbench/control.py --workload smollm360m.warm \
        --seeds 11,12,13 --control-seeds 11,12 --seconds 8 \
        --faults wrong_task,zero_xattn --fault-seeds 11,12,13

For each seed the program is built, set up and serves the cell's own mix
for ``--seconds`` (long enough to finish its longest requests), exactly
as a run does; the same sample of requests a run would compare is read by
the float32 reference, and the widest gap by which a served token's
reference logit lies below the reference's best is printed.  On the
control seeds the control is read too: the reference computed with every
linear layer in float8 (e4m3) put in the program's place, at each
position of the same prompts and served tokens, the gap of the token it
ranks first.  On the fault seeds, each named fault of
``chipbench/faults.py`` is planted in the program in turn, and the
faulty program's reading is taken the same way.  One JSON line per
seed and program.
"""

import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, seconds: float, control: bool, say,
             tokens=None, fault=None) -> dict:
    """The program's reading on one seed (a run's window of ``seconds``
    and its sample), and the control's on the same sample if asked.
    With ``fault`` (a name in ``faults.FAULTS``) the program is broken."""
    from chipbench import bench, faults, traffic

    tr = traffic.generate(cell.mix, cell.config["vocab_size"], seed, seconds)
    with faults.FAULTS[fault]() if fault else contextlib.nullcontext():
        r = bench.Run(cell, tr, seed, say)
        r.setup()
        served = r.serve(tr.queries)
    picked = bench.sample(served, tr, seed, tokens or bench.SAMPLE_TOKENS)
    shots, queries, want = bench.reference_inputs(served, tr, picked)
    r.free()
    ref = cell.reference.logits(cell.config, seed, shots, queries)
    gaps = bench.logit_gaps(ref, want)
    out = {"seed": seed, "fault": fault,
           "incomplete": bench.incomplete(served),
           "tokens": len(want), "tasks": len(shots),
           "program_gap": float(gaps.max()),
           "program_flips": int((gaps > 0).sum())}
    if control:
        low = cell.reference.logits(cell.config, seed, shots, queries,
                                    control=True)
        cg = bench.logit_gaps(ref, low.argmax(1))
        out.update(control_gap=float(cg.max()),
                   control_flips=int((cg > 0).sum()))
    return out


def main(argv=None) -> int:
    import argparse
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import run, spec

    cell = spec.load_cell(args.workload)
    import jax

    run.find_devices(jax, cell.chips)
    run.enable_compile_cache(jax)
    seeds = lambda text: [int(s) for s in text.split(",") if s]
    controls = set(seeds(args.control_seeds))
    jobs = [(s, None) for s in seeds(args.seeds)]
    jobs += [(s, f) for f in args.faults.split(",") if f
             for s in seeds(args.fault_seeds)]
    for seed, fault in jobs:
        t0 = time.time()
        out = readings(cell, seed, args.seconds,
                       fault is None and seed in controls, run.say,
                       fault=fault)
        out["seconds"] = time.time() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)  # the checkout, not this script's directory
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
