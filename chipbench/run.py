"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload smollm360m.warm --seed 7 \
        --seconds 30 --trace 0

Set-up (timed as ``setup_s``, from process start): the cell's seeded
weights are made on the device in one jitted call; the serving engine is
built with the paged KV layout and the MemCom compressor attached; every
task of the mix is compressed through the engine's own online compiler;
one serve warms each prefill bucket and the decode step.  Then the
window: the mix's requests are served by one ``ServingEngine.serve``
call, open loop (each request due at its ``arrival_s``).  ``--trace 1``
runs the same window, capped at ``bench.TRACE_CAP_S`` seconds of
arrivals, under the JAX profiler and reports the per-layer metrics
instead of the end-to-end ones.

Afterwards every device buffer of the program is freed and the float32
reference (``chipbench/reference``) reads a sample of the served
requests, drawn from the seed with the longest among them: ``correct``
holds when every request finished with all its tokens and no served
token's reference logit lies further below the reference's best than the
configuration's limit.  ``--control 1`` puts the control in the
program's place in that comparison (the reference in float8, at each
position of the same prompts and served tokens), and must come out not
correct.

The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the last key of that object.  Without an accelerator, or with
fewer chips than the cell asks for, the run exits with code 2 and prints
no result.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / "chipbench_out" / "trace"


def use_checkout() -> None:
    """Import the benchmark and the program from this checkout, never
    from the script's own directory."""
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the control (the float8 reference) in "
                    "the program's place; such a run must read not correct")
    return ap.parse_args(argv)


def find_devices(jax, chips: int):
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no accelerator: {e}") from e
    d0 = devs[0]
    if d0.platform == "cpu":
        raise NoChip(f"JAX found only {len(devs)} cpu device(s), "
                     f"kind {d0.device_kind!r}: no accelerator")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)} "
                     f"{d0.platform} device(s)")
    return devs[:chips]


def enable_compile_cache(jax) -> str:
    """Persistent compilation cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), caching every program
    so that a second run of a cell compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    args = parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    from chipbench import spec

    cell = spec.load_cell(args.workload)
    import jax

    try:
        devs = find_devices(jax, cell.chips)
    except NoChip as e:
        say(str(e))
        return 2
    peak = spec.load_peaks(devs[0].device_kind)
    say(f"{cell.name}: seed {args.seed}, {args.seconds:g} s, trace "
        f"{args.trace}; {len(devs)} x {devs[0].device_kind}; compile "
        f"cache {enable_compile_cache(jax)}")
    from chipbench import bench

    result = bench.run_cell(cell, devs, peak, seed=args.seed,
                            seconds=args.seconds, trace=bool(args.trace),
                            t_start=T_START, trace_dir=TRACE_DIR, log=say,
                            control=bool(args.control))
    for name, c in result["checks"].items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    use_checkout()
    sys.exit(main())
