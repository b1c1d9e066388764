"""One run of one cell: set up, measure a window, check the outputs
against the plain reference, print one result line.

The last line on stdout is the result object; the lines before it on
stdout are JSON records of what the run saw (set-up, each solve,
compiles in the window).  The last lines on stderr are the checks, one
per line, each number beside its limit.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

from harness import spec as S

ROOT = Path(__file__).resolve().parents[2]
# --save-trace keeps this much of the traced window (a small sample)
SAMPLE_NS = 50e6


class Ctx:
    """What a system sees of the run."""

    def __init__(self, args, cell):
        self.workload = args.workload
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.control = args.control
        self.rate = args.rate
        self.config = _merged(cell["config"], args.rehearse)
        self.traffic = _merged(cell["traffic"], args.rehearse)
        self.ref = S.reference(self.config["reference"])
        self.state = None
        self.traced = None
    def info(self, what, **kw):
        line = {"what": what, **kw}
        print(json.dumps(line, default=_jsonable), flush=True)



def _merged(d: dict, rehearse: bool) -> dict:
    """A config or traffic dict, with its ``rehearse`` overrides merged
    in (one level deep) for the CPU rehearsal."""
    out = {k: v for k, v in d.items() if k != "rehearse"}
    if rehearse:
        for k, v in d.get("rehearse", {}).items():
            out[k] = dict(out.get(k, {}), **v) if isinstance(v, dict) \
                and isinstance(out.get(k), dict) else v
    return out


def _jsonable(v):
    try:
        return v.item()
    except AttributeError:
        return str(v)


class TracedWindow:
    """The profiler session and the ``bench.window`` span, over the
    whole window: stopping the profiler inside an open loop would stall
    its generator for as long as the stop takes."""

    def __init__(self, save_to=None):
        from harness import tracing

        self.profile = tracing.Profile()
        self.save_to = save_to
        self.note = None

    def begin(self):
        from harness import tracing

        self.profile.start()
        self.note = tracing.annotate("bench.window")
        self.note.__enter__()

    def end(self):
        if self.note is not None:
            self.note.__exit__(None, None, None)
            self.note = None
            self.profile.stop()

    def reduce(self):
        import trace_reduce

        self.end()
        try:
            if self.profile.path is None:
                return None
            trace = trace_reduce.load_xplane(self.profile.path)
            if self.save_to:
                lo, _ = trace_reduce.window_of(trace)
                sample = trace_reduce.clip_trace(trace, lo, lo + SAMPLE_NS)
                Path(self.save_to).parent.mkdir(parents=True, exist_ok=True)
                Path(self.save_to).write_text(json.dumps(sample))
            return trace_reduce.reduce_trace(trace)
        finally:
            self.profile.close()


def parse(argv):
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print its result "
                    "line (see BENCHMARK.json).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never a measurement")
    ap.add_argument("--control", choices=("bf16",), default=None,
                    help="replace the program's outputs by the reference "
                         "in bfloat16; the checks must then fail")
    ap.add_argument("--rate", type=float, default=None,
                    help="override an open loop's rate (capacity sweep)")
    ap.add_argument("--save-trace", default=None,
                    help="write the first 50 ms of the normalized "
                         "profiler trace here (JSON)")
    return ap.parse_args(argv)


def _fail(msg: str, code: int = 1) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None, t_process: float = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        return _fail(f"no program under {src}: run from a full checkout", 2)
    try:
        cell = S.load_cell(ROOT, args.workload)
    except (S.SpecError, KeyError, ValueError) as e:
        return _fail(f"bad benchmark spec: {e}", 2)
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    need = int(cell["cell"]["chips"])
    if platform != "tpu" and not args.rehearse:
        return _fail(f"jax found no TPU (platform {platform!r}); this run "
                     f"measures the chip and stops here")
    if len(devices) < need:
        return _fail(f"cell needs {need} chips, jax has {len(devices)}")
    peaks = None
    if platform == "tpu":
        from harness.peaks import UnknownDevice, peaks_for

        try:
            peaks = peaks_for(kind)
        except UnknownDevice as e:
            return _fail(str(e))

    from harness.clocks import CompileClock
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # keep every program, however quick to compile, so that each run
    # after a cell's first loads all of them from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock().install(jax)
    ctx = Ctx(args, cell)
    system = S.system(ctx.config["system"])
    ctx.info("start", workload=args.workload, seed=args.seed,
             seconds=args.seconds, trace=args.trace, control=args.control,
             platform=platform, device_kind=kind, count=len(devices),
             jax=jax.__version__, compile_cache=cache_dir)

    ctx.state = state = system.setup(ctx)
    setup_s = time.perf_counter() - t_process
    c0 = clock.snapshot()
    if ctx.trace:
        ctx.traced = TracedWindow(args.save_trace)
        ctx.traced.begin()
    rec = system.window(ctx, state)
    c1 = clock.snapshot()
    reduced = ctx.traced.reduce() if ctx.traced is not None else None
    # read before the reference runs: a process's peak never falls
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:need])
    in_window = CompileClock.delta(c1, c0)
    ctx.info("window_compiles", **in_window)

    verdict = system.check(ctx, state, rec)
    limits = ctx.config["limits"]
    checks = verdict["checks"]
    ok = verdict["failed"] == 0
    lines = {}
    for name, value in checks.items():
        lim = limits.get(name)
        passed = lim is not None and value == value and value <= lim
        ok = ok and passed
        lines[name] = {"value": value, "limit": lim}

    device = {"platform": platform, "kind": kind, "device_kind": kind,
              "count": len(devices), "memory_peak_bytes": peak or None}
    if not ctx.trace:
        values = dict(system.end_to_end(ctx, state, rec), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] in values}
        breakdown = None
    else:
        run = system.layer_run(ctx, state, rec)
        run.update(trace=reduced, peaks=peaks, config=ctx.config)
        metrics = {}
        for m in cell["per_layer"]:
            v = S.metric_reader(m["name"])(run)
            if v is not None and not (isinstance(v, float) and math.isnan(v)):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            ctx.info("trace", n_device_planes=reduced["n_device_planes"],
                     idle_share=reduced["idle_share"])
        else:
            breakdown = None

    result = {"correct": bool(ok), "attempted": verdict["attempted"],
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = lines
    for name, c in lines.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    text = json.dumps(result, default=_jsonable)
    print(text, flush=True)
    return 0
