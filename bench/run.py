"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for.  The cell, its configuration, its job and its per-layer metric
readers are found by name from ``BENCHMARK.json`` (``bench/lib/spec.py``).
Set-up builds the weights, data and state from ``--seed`` and warms every
program the window uses; the window then runs for ``--seconds``; the
comparison with the plain reference runs after it.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared beside its limit.  Without a TPU, or with fewer chips than
the cell asks for, it exits 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def build(spec, name: str, seed: int, seconds: float, tracing: bool):
    """Construct the cell's job (its set-up runs here)."""
    from bench.lib import spans
    cell = spec.cell(name)
    cfg = spec.config_file(cell["config"])
    ctx = types.SimpleNamespace(
        name=name, cell=cell, config=cfg, workload=spec.workload_file(name),
        seed=seed, seconds=seconds, tracing=tracing, rec=spans.Recorder(),
        spec=spec,
        reference=spec.reference(cfg["reference"]))
    return ctx, spec.job(cfg["job"]).Job(ctx)


def per_layer(spec, ctx, job, win, trace_dir: str, dev: dict):
    """(metrics, busy_s, breakdown) of a traced run."""
    from bench.lib import peaks, trace
    tr = trace.load(trace_dir)
    ops = [e for d in tr.device_ops for e in d]
    print(f"[trace] {len(ops)} device ops over [{min(e.start for e in ops)},"
          f" {max(e.end for e in ops)}] s; window {tr.window}; "
          f"{len(tr.host_spans)} host spans", flush=True)
    lo, hi = win.traced
    reading = types.SimpleNamespace(
        window_s=hi - lo, t0=lo, t1=hi, trace=tr, job=job,
        steps=[s for s in job.steps if lo <= s["t0"] and s["t1"] <= hi],
        spans=[s for s in ctx.rec.spans if lo <= s[1] and s[2] <= hi],
        config=ctx.config, workload=ctx.workload, chips=dev["count"],
        peaks=peaks.peaks(dev["kind"]))
    metrics = {}
    for m in spec.per_layer(ctx.name):
        value = spec.reader(m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {"device_ops": trace.top_ops(tr),
                 "idle_gaps": trace.idle_gaps(tr)}
    return metrics, trace.busy_s(tr), tr.window_s, breakdown


def main(argv=None) -> int:
    args = _args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.lib import compiles, device, spec as spec_lib, window

    try:
        dev = device.require(spec_lib.Spec.load(ROOT).cell(
            args.workload)["chips"])
    except device.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    counter = compiles.Counter()
    spec = spec_lib.Spec.load(ROOT)
    ctx, job = build(spec, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    setup_s = time.perf_counter() - T_START
    print(f"[bench] set-up {setup_s:.3f} s: {counter.snapshot()} "
          f"(compile cache {cache_dir})", flush=True)

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench", "trace",
                                 f"{args.workload}.{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    before = counter.snapshot()
    with window.Window(args.seconds, trace_dir) as win:
        job.run(win)
    job.after_window()
    print(f"[bench] window {win.elapsed:.3f} s: {counter.since(before)}",
          flush=True)
    device_info = {**dev, "memory_peak_bytes":
                   device.memory_peak_bytes(dev["count"])}

    if args.trace:
        from bench.lib import trace
        metrics, busy, traced_s, breakdown = per_layer(
            spec, ctx, job, win, trace.find_xplane(trace_dir), dev)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device_info.update(busy_s=busy, window_s=traced_s)
    else:
        e2e = job.end_to_end(win)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end(args.workload)}
    attempted, failed = job.counts()
    job.release()
    t_check = time.perf_counter()
    checks = job.check()
    print(f"[bench] check {time.perf_counter() - t_check:.3f} s", flush=True)
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and failed == 0
    for name, c in checks.items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"[check] failed = {failed} (limit 0)", file=sys.stderr)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device_info}
    if args.trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
