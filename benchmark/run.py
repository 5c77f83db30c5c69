#!/usr/bin/env python3
"""One cell of the chip benchmark, once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are found by the names in
``BENCHMARK.json``; this file knows none of them.  Every line but the last
is a JSON record worth keeping; the last line of a measured run is the
result the driver reads.  Without a TPU, or with fewer chips than the cell
asks for, nothing is run and the exit code is 1.

    --rehearse   toy widths on any platform through the same code; never
                 prints a line the driver could take for a result
    --variant    a named override of the configuration file (``variants``),
                 e.g. the low-precision control of ``correct``
    --sweep      the traffic file's ``sweep`` instead of the window: rates
                 stepped in one process after one set-up (no result line)
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmark")


def say(record: str, **fields) -> None:
    print(json.dumps({"record": record, **fields}, default=str), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def find_cell(bench: dict, args) -> tuple:
    """(cell, configuration file, traffic file, metrics of the cell)."""
    if args.workload:
        cells = [c for c in bench["workloads"] if c["name"] == args.workload]
        if not cells:
            raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
        cell = cells[0]
    else:  # files that no cell of BENCHMARK.json names yet
        cell = {"name": f"{args.config}.{args.traffic}", "config": args.config,
                "traffic": args.traffic, "chips": 1}
    files = {c["name"]: c["file"] for c in bench["configs"]}
    cfg_path = os.path.join(ROOT, files[cell["config"]]) \
        if cell["config"] in files \
        else os.path.join(HERE, "configs", cell["config"] + ".json")
    config = load_json(cfg_path)
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")

    def mine(m: dict, reported: set | None) -> bool:
        if "workloads" in m:
            return cell["name"] in m["workloads"]
        return reported is None or m.get("moves") in reported

    e2e = [m for m in bench["end_to_end"] if mine(m, None)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if mine(m, names)]
    if not args.workload:  # such a mix says itself what it reports
        reports = traffic.get("reports", {})
        e2e += reports.get("end_to_end", [])
        layer = reports.get("per_layer", [])
    return cell, config, traffic, e2e, layer


def read_metrics(metrics: list, run) -> dict:
    out = {}
    for m in metrics:
        spec = load_json(HERE, "metrics", m["name"] + ".json")
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        value = reader.read(spec, run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class Tracer:
    """Traces ``seconds`` of the window from ``offset`` on, in a thread of
    its own, and keeps the counters at both ends."""

    def __init__(self, sut, offset: float, seconds: float):
        self.sut, self.offset, self.seconds = sut, offset, seconds
        self.dir = tempfile.mkdtemp(prefix="pw_bench_trace_")
        self.window = None
        self.counters = None
        self.error = None
        self._th = None

    def arm(self, t0: float) -> None:
        self._th = threading.Thread(target=self._run, args=(t0,),
                                    name="bench-tracer")
        self._th.start()

    def _run(self, t0: float) -> None:
        import jax

        try:
            time.sleep(max(t0 + self.offset - time.perf_counter(), 0.0))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            a, c0 = time.perf_counter(), self.sut.counters()
            time.sleep(self.seconds)
            b, c1 = time.perf_counter(), self.sut.counters()
            jax.profiler.stop_trace()
            self.window = (a, b)
            self.counters = {k: c1[k] - c0[k] for k in c1}
        except Exception as exc:  # noqa: BLE001 - reported by reduce()
            self.error = exc

    def reduce(self, chips: int):
        from benchmark import trace_reduce

        self._th.join()
        if self.error is not None:
            raise self.error
        try:
            return trace_reduce.Trace(trace_reduce.load(
                trace_reduce.find_xplane(self.dir)), n_devices=chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Gauges:
    """Polls the system's gauges (``sut.gauges()``: levels, not counts,
    such as cache blocks in use) through the window, in a thread of its
    own, and keeps each one's mean and peak."""

    def __init__(self, sut, every_s: float = 0.25):
        self.read = getattr(sut, "gauges", None)
        self.every_s = every_s
        self.rows: list = []
        self._stop = threading.Event()
        self._th = threading.Thread(target=self._run, name="bench-gauges",
                                    daemon=True)

    def start(self) -> None:
        if self.read is not None:
            self._th.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            self.rows.append(self.read())
            self._stop.wait(self.every_s)

    def stop(self) -> dict:
        self._stop.set()
        if self._th.is_alive():
            self._th.join()
        return {k: {"mean": sum(r[k] for r in self.rows) / len(self.rows),
                    "peak": max(r[k] for r in self.rows),
                    "polls": len(self.rows)}
                for k in (self.rows[0] if self.rows else ())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--variant")
    ap.add_argument("--config", help="in place of --workload, for files "
                    "that no cell names yet: a configuration by name")
    ap.add_argument("--traffic", help="and a traffic file by name")
    args = ap.parse_args(argv)
    if not args.workload and not (args.config and args.traffic):
        ap.error("--workload is required (or --config and --traffic)")
    if not os.path.isdir(os.path.join(ROOT, "pathway_tpu")):
        print("benchmark: the system under test (pathway_tpu/) is not in "
              f"{ROOT}; nothing was run", file=sys.stderr)
        return 1
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic, e2e, layer = find_cell(bench, args)
    if args.variant:
        config = merged(config, config["variants"][args.variant])
    if args.rehearse:
        config = merged(config, config.get("rehearse", {}))
        traffic = merged(traffic, traffic.get("rehearse", {}))
    seconds = args.seconds if args.seconds is not None else (
        traffic.get("rehearse_seconds", 4.0) if args.rehearse
        else float(bench["run_seconds"]))

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"benchmark: no TPU here ({device}); nothing was run",
              file=sys.stderr)
        return 1
    if len(devs) < cell["chips"]:
        print(f"benchmark: {cell['name']} needs {cell['chips']} chips, found "
              f"{len(devs)}; nothing was run", file=sys.stderr)
        return 1
    used = devs[: cell["chips"]]

    from benchmark import flops
    from pathway_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    peaks = flops.peaks(device["kind"]) if device["platform"] == "tpu" \
        else None
    say("environment", cell=cell["name"], config=cell["config"],
        traffic=cell["traffic"], seed=args.seed, seconds=seconds,
        trace=args.trace, rehearsal=args.rehearse, variant=args.variant,
        device=device, compile_cache_dir=cache_dir, jax=jax.__version__)

    system = importlib.import_module("benchmark.systems." + config["system"])
    generator = importlib.import_module(
        "benchmark.generators." + traffic["generator"])
    sut = system.build(config, args.seed, args.rehearse)
    try:
        t_built = time.perf_counter()
        generator.warm(sut, traffic, args.seed)
        say("setup", build_s=t_built - T_PROCESS,
            warm_s=time.perf_counter() - t_built, info=sut.info)
        if args.sweep:
            generator.sweep(sut, traffic, args.seed, say)
            return 0
        tracer = Tracer(sut, traffic["trace"]["offset_s"],
                        traffic["trace"]["seconds"]) if args.trace else None
        mark: dict = {}
        gauges = Gauges(sut)

        def on_start(t0: float) -> None:
            mark["setup_s"] = t0 - T_PROCESS
            mark["compiles0"] = sut.compile_count()
            mark["c0"] = sut.counters()
            say("window_start", setup_s=mark["setup_s"], wall=time.time())
            gauges.start()
            if tracer is not None:
                tracer.arm(t0)

        def on_end(t1: float) -> None:
            mark["gauges"] = gauges.stop()
            mark["c1"] = sut.counters()
            mark["compiles1"] = sut.compile_count()
            say("window_end", seconds=t1 - T_PROCESS - mark["setup_s"],
                wall=time.time())

        observed = generator.run(sut, traffic, args.seed, seconds,
                                 on_start, on_end)
        counters = {k: mark["c1"][k] - mark["c0"][k] for k in mark["c1"]}
        counters.update(observed["counters"])
        observed["counters"] = counters
        samples = dict(observed["samples"])
        samples.update(sut.samples(counters))
        samples["setup_s"] = [mark["setup_s"]]
        t_red = time.perf_counter()
        trace = tracer.reduce(cell["chips"]) if tracer is not None else None
        if trace is not None:
            d0, d1 = trace.device_span()
            say("trace_reduced", seconds=time.perf_counter() - t_red,
                host_clock_s=tracer.window[1] - tracer.window[0],
                device_span_s=d1 - d0, lines=trace.line_names())
        peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in used)
        info = dict(sut.info)
        say("window", attempted=observed["attempted"],
            failed=observed["failed"], lateness_ms=observed["lateness_ms"],
            counters=counters, gauges=mark["gauges"],
            steady=observed.get("steady"),
            compiles_in_window=mark["compiles1"] - mark["compiles0"])
        sut.release()
        compared = sut.verify(observed, args.seed)
        compared.append({"name": "compiles_in_window", "limit": 0,
                         "value": mark["compiles1"] - mark["compiles0"]})
    finally:
        sut.close()

    run = types.SimpleNamespace(
        counters=counters, samples=samples, info=info, trace=trace,
        trace_window=tracer.window if tracer else None,
        trace_counters=tracer.counters if tracer else None,
        events=observed.get("events", {}), peaks=peaks,
        window=(observed["t0"], observed["t1"]), chips=cell["chips"])
    metrics = read_metrics(layer if args.trace else e2e, run)
    dev = dict(device, count=cell["chips"], memory_peak_bytes=peak_bytes)
    result = {"correct": all(c["value"] <= c["limit"] for c in compared),
              "attempted": observed["attempted"], "failed": observed["failed"],
              "metrics": metrics, "device": dev}
    if trace is not None:
        a, b = tracer.window
        dev["busy_s"] = trace.busy_s()
        dev["window_s"] = b - a
        s0, s1 = trace.span()
        result["breakdown"] = {"device_ops": trace.device_ops(),
                               "idle_gaps": trace.idle_gaps(s0, s1)}
    result["compared"] = {c["name"]: {k: v for k, v in c.items()
                                      if k != "name"} for c in compared}
    for c in compared:
        print(f"compared {c['name']}: value {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    if args.rehearse:
        say("rehearsal", note="a rehearsal proves nothing about the chip",
            would_be=result)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
