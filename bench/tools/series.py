"""Run several benchmark runs one after another, each in its own
process, and keep what each printed.

    python bench/tools/series.py --out series_out \
        --run "delaunay_n17.multilevel 11 30 0" --run "delaunay_n17.multilevel 12 30 1"

Each ``--run`` is "<workload> <seed> <seconds> <trace> [extra args...]".
Every run's stdout and stderr go to ``<out>/<i>_<workload>_<seed>.out``
and ``.err``; one summary line per run (exit code, wall seconds and the
result line) is printed and appended to ``<out>/summary.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import shlex
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parents[1] / "run.py"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--run", action="append", default=[])
    ap.add_argument("--timeout", type=float, default=1200.0)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    worst = 0
    for i, spec in enumerate(args.run):
        w, seed, secs, trace, *extra = shlex.split(spec)
        cmd = [sys.executable, str(RUN), "--workload", w, "--seed", seed,
               "--seconds", secs, "--trace", trace, *extra]
        stem = out / f"{i:02d}_{w}_{seed}"
        t0 = time.perf_counter()
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout)
            rc, so, se = p.returncode, p.stdout, p.stderr
        except subprocess.TimeoutExpired as e:
            rc = 124
            so = e.stdout.decode() if isinstance(e.stdout, bytes) else (
                e.stdout or "")
            se = e.stderr.decode() if isinstance(e.stderr, bytes) else (
                e.stderr or "")
        wall = time.perf_counter() - t0
        stem.with_suffix(".out").write_text(so)
        stem.with_suffix(".err").write_text(se)
        last = so.strip().splitlines()[-1] if so.strip() else ""
        try:
            result = json.loads(last)
        except ValueError:
            result = None
        line = {"i": i, "run": spec, "rc": rc, "wall_s": wall,
                "result": result}
        if result is None:
            line["stderr_tail"] = se[-1500:]
        with open(out / "summary.jsonl", "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
        worst = max(worst, rc)
    return worst


if __name__ == "__main__":
    sys.exit(main())
