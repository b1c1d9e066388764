"""Read a cell's checked numbers on many seeds in one process, for
setting limits: sound runs, the bfloat16 control on the same outputs,
and runs with a planted fault (``bench/harness/faults.py``).  Also
serves an open loop's capacity sweep (``rate=``).

    python bench/tools/readings.py --workload gn_sbm_stream.poisson \\
        --seconds 51 --out readings.jsonl \\
        --run 11 --run 12,control --run 13,fault=lane_swap --run 14,rate=3

Each ``--run`` is "<seed>[,control][,fault=<name>][,rate=<r>]";
``control`` also checks the run's outputs as the control.  One JSON
line per run (end-to-end numbers, checks, and the control's checks) is
printed and appended to ``--out``.  The set-up of a cell whose inputs do
not depend on the seed is made once (``--reuse-setup``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def _spec(text):
    parts = text.split(",")
    run = {"seed": int(parts[0]), "control": False, "fault": "none",
           "rate": None}
    for p in parts[1:]:
        if p == "control":
            run["control"] = True
        elif p.startswith("fault="):
            run["fault"] = p[6:]
        elif p.startswith("rate="):
            run["rate"] = float(p[5:])
        else:
            raise SystemExit(f"bad run spec {text!r}")
    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--run", action="append", default=[])
    ap.add_argument("--out", required=True)
    ap.add_argument("--reuse-setup", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from harness import faults
    from harness import runner
    from harness import spec as S

    cell = S.load_cell(runner.ROOT, args.workload)
    sys.path.insert(0, str(runner.ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    state = None
    for text in args.run:
        run = _spec(text)
        ns = argparse.Namespace(
            workload=args.workload, seed=run["seed"], seconds=args.seconds,
            trace=0, control=None, rate=run["rate"], rehearse=args.rehearse)
        ctx = runner.Ctx(ns, cell)
        system = S.system(ctx.config["system"])
        undo = faults.plant(run["fault"])
        try:
            t0 = time.perf_counter()
            if state is None or not args.reuse_setup:
                state = system.setup(ctx)
            ctx.state = state
            t1 = time.perf_counter()
            rec = system.window(ctx, state)
            t2 = time.perf_counter()
        finally:
            undo()
        line = {"run": text, "setup_s": t1 - t0, "window_wall_s": t2 - t1,
                "e2e": system.end_to_end(ctx, state, rec),
                "checks": system.check(ctx, state, rec)}
        if run["control"]:
            ctx.control = "bf16"
            line["control_checks"] = system.check(ctx, state, rec)
        text_line = json.dumps(line, default=runner._jsonable)
        with open(out, "a") as f:
            f.write(text_line + "\n")
        print(text_line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
