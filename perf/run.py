#!/usr/bin/env python3
"""The benchmark's one command.

  python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process every time: find the cell in BENCHMARK.json, require the
chips it asks for (no CPU path), turn on the persistent compilation cache
at its fixed place inside the checkout, let the cell's system runner
build, check against the plain reference, warm up and measure for
`--seconds`, then print ONE JSON object as the last line of stdout
(everything else goes to stderr). `--trace 0` reports the cell's
end-to-end metrics and starts no profiler; `--trace 1` profiles a short
steady stretch inside the window and reports its per-layer metrics, the
device's busy time and a breakdown.

`--rehearse [MANIFEST]` is for the CPU rehearsal and the tests only (the
driver never passes it): it reads another manifest (default
perf/rehearse.json, tiny configurations), accepts whatever platform jax
has, and keeps no compilation cache. Its numbers are written nowhere.
"""
import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)
for _p in (ROOT, PERF_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_NULL = contextlib.nullcontext()


class Tracer:
    """jax.profiler around a stretch of the window, and the harness's own
    spans inside it. With tracing off every method does nothing."""

    def __init__(self, enabled, out_dir):
        self.enabled, self.out_dir = enabled, out_dir
        self.running = self.done = False

    def start(self):
        if not self.enabled or self.running or self.done:
            return
        import jax.profiler as jp
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0     # host spans are the bench.* ones
        jp.start_trace(self.out_dir, profiler_options=opts)
        self.running = True

    def stop(self):
        if self.running:
            import jax.profiler as jp
            jp.stop_trace()
            self.running, self.done = False, True

    def span(self, name):
        if not self.running:
            return _NULL
        import jax.profiler as jp
        return jp.TraceAnnotation(name)

    def xplane(self):
        found = glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
        return found[0] if self.done and found else None


class Context:
    def __init__(self, cell, args, tracer):
        self.cell, self.tracer = cell, tracer
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.rehearse = args.rehearse is not None

    @staticmethod
    def log(msg):
        print(f"[perf] {msg}", file=sys.stderr, flush=True)


def require_device(cell, rehearse):
    """The device as jax reports it; RuntimeError when it is not the
    accelerator the cell needs."""
    import jax
    from paddle_tpu.chip import device_stamp, require_tpu
    stamp = device_stamp() if rehearse else require_tpu()
    if len(jax.devices()) < cell.chips:
        raise RuntimeError(
            f"workload {cell.name!r} needs {cell.chips} chips, jax "
            f"reports {len(jax.devices())}")
    return {"platform": stamp["platform"], "kind": stamp["kind"],
            "count": stamp["count"]}


def keep_every_executable():
    """The persistent compilation cache at the program's fixed place
    (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), holding every
    executable — also the small ones that compile in under a second, which
    jax leaves out by default: the second run of a cell compiles nothing."""
    import jax
    from paddle_tpu.chip import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _memory_peak(n_chips):
    import jax
    peaks = []
    for d in jax.devices()[:n_chips]:
        ms = d.memory_stats()
        if ms:
            peaks.append(int(ms["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def main(argv=None, out=sys.stdout):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", nargs="?", const="perf/rehearse.json",
                    default=None, metavar="MANIFEST")
    args = ap.parse_args(argv)

    # a directory that holds only the benchmark has no program: this
    # import fails, the exit code is not 0 and nothing is printed
    import paddle_tpu.chip  # noqa: F401
    from harness import manifest, peaks, trace_reduce
    from harness.clock import CompileClock

    cell = manifest.Cell(
        manifest.load_json(args.rehearse or "BENCHMARK.json"), args.workload)
    rehearse = args.rehearse is not None
    if rehearse:    # a CPU stands in for `chips` chips; a no-op elsewhere
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={cell.chips}")
    if not rehearse:
        keep_every_executable()
    device = require_device(cell, rehearse)
    clock = CompileClock()
    tracer = Tracer(bool(args.trace),
                    os.path.join(ROOT, ".perf_trace", cell.name))
    ctx = Context(cell, args, tracer)
    ctx.log(f"{cell.name}: config {cell.config_name}, traffic "
            f"{cell.traffic_name}, {device}")

    runner = manifest.load_plugin("systems", cell.config["system"])
    rec = runner.run(ctx)
    tracer.stop()

    t_ws, t_we = rec["window"]
    device["memory_peak_bytes"] = _memory_peak(cell.chips)
    rec["t_process_start"] = T_PROCESS_START
    rec["device"] = device
    rec["peaks"] = (peaks.peaks_for(device["kind"])
                    if device["platform"] == "tpu" else None)
    rec["clock"] = {
        "compile_s_setup": clock.compile_s(before=t_ws),
        "trace_lower_s": clock.trace_lower_s,
        "cache_hits": clock.cache_hits, "cache_misses": clock.cache_misses,
        "in_window": clock.obtained_between(t_ws, t_we)}
    if rec["clock"]["in_window"]:
        ctx.log(f"COMPILED INSIDE THE WINDOW: {rec['clock']['in_window']}")
    path = tracer.xplane()
    rec["trace"] = trace_reduce.reduce_trace(path, cell.chips) if path \
        else None

    # every reader runs in every run and is logged; the line carries the
    # cell's end-to-end metrics untraced and its per-layer metrics traced
    read = {}
    for kind, wanted in (("end_to_end", cell.end_to_end),
                         ("layer_metrics", cell.per_layer)):
        read[kind] = {}
        for m in wanted:
            value = manifest.load_plugin(kind, m["name"]).read(rec)
            if value is not None:
                read[kind][m["name"]] = {"value": value, "unit": m["unit"]}
    ctx.log("all readers: " + json.dumps(
        {n: v["value"] for d in read.values() for n, v in d.items()}))
    metrics = read["layer_metrics" if args.trace else "end_to_end"]
    line = {"correct": bool(rec["correct"]), "attempted": rec["attempted"],
            "failed": rec["failed"], "metrics": metrics, "device": device}
    if rec["trace"]:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        line["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                             "idle_gaps": rec["trace"]["idle_gaps"]}
    ctx.log(f"checks: {rec['checks']}")
    print(json.dumps(line), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
