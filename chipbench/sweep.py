"""Find a cell's knee: the highest offered rate whose backlog does not
grow over the window.  One set-up, then one open-loop window per rate.

    python3 chipbench/sweep.py --workload smollm360m.warm --seed 1 \
        --seconds 20 --rates 20,40,60

With ``--rates`` omitted it serves one window at the mix's own rate.  A
window's backlog grew when the median time to first token of its last quarter of
requests exceeds 1.25 times that of its first quarter plus 50 ms; the
knee is the highest rate below the first such window.  Prints one JSON line per
window.
"""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def window_report(served, rate) -> dict:
    import numpy as np

    from chipbench import bench

    log = [served.log[u] for u in served.uids]
    order = np.argsort([r["arrival_s"] for r in log])
    ttft = np.asarray([log[i]["first_token_s"] - log[i]["arrival_s"]
                       for i in order])
    q = max(1, len(ttft) // 4)
    first, last = float(np.median(ttft[:q])), float(np.median(ttft[-q:]))
    out = {"offered_per_s": rate, "requests": len(log),
           "seconds": served.seconds,
           "completed_per_s": len(log) / served.seconds,
           "ttft_p50_ms": 1e3 * float(np.median(ttft)),
           "first_quarter_ttft_p50_ms": 1e3 * first,
           "last_quarter_ttft_p50_ms": 1e3 * last,
           "grew": bool(last > 1.25 * first + 0.05),
           "incomplete": bench.incomplete(served),
           "window_compiles": served.compiles}
    out.update(bench.end_to_end(served))
    return out


def main(argv=None) -> int:
    import argparse
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import bench, run, spec, traffic

    cell = spec.load_cell(args.workload)
    import jax

    run.find_devices(jax, cell.chips)
    run.enable_compile_cache(jax)
    rates = [float(r) for r in args.rates.split(",") if r]
    mix = dict(cell.mix)
    t = traffic.generate(mix, cell.config["vocab_size"], args.seed, 1.0)
    r = bench.Run(cell, t, args.seed, run.say)
    t0 = time.time()
    phases = r.setup()
    run.say(f"set-up {time.time() - t0:.1f} s {phases}")
    for rate in rates or [mix["arrivals"]["rate_per_s"]]:
        mix["arrivals"] = dict(mix["arrivals"], rate_per_s=rate)
        w = traffic.generate(mix, cell.config["vocab_size"], args.seed,
                             args.seconds)
        served = r.serve(w.queries)
        print(json.dumps(window_report(served, rate)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)  # the checkout, not this script's directory
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
