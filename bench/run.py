"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name, from `BENCHMARK.json` at
the root of the checkout:

- the cell's configuration file (`file` of its `configs` entry);
- its traffic mix, `bench/traffic/<traffic>.json`, which names a driver;
- the driver, `bench/drivers/<driver>.py`, which drives one entry of the
  program (`setup`, `window`, `release`, `check`, `end_to_end`);
- the limits of its comparison, `bench/limits/<cell>.json`;
- each per-layer metric, `bench/metrics/<metric>.py` (`read`).

The run prints the device first, and fails (exit 3, no result) where JAX
finds no TPU or fewer chips than the cell asks for.  Set-up runs from the
start of the process to the start of the window.  With ``--trace 1`` the
driver traces a few seconds inside the window, and the result carries
the per-layer metrics in place of the end-to-end ones.  The last line of
standard output is one JSON object; the numbers compared for `correct`
come last in it and on standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench: Path = BENCH):
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


@dataclass
class Cell:
    """One workload of `BENCHMARK.json` with everything it names."""

    name: str
    entry: dict
    config: dict          # the configuration file
    traffic: dict         # the traffic file
    limits: dict          # the comparison's limits
    bench: dict           # the whole BENCHMARK.json
    root: Path = ROOT     # the checkout it was found in

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    @property
    def model(self) -> dict:
        """The sizes as run (the reference's and the FLOP functions' input)."""
        return self.config["run"]

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if applies(m, self.name)]

    def per_layer(self) -> list:
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if m["moves"] in reported and applies(m, self.name)]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(root / conf["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    limits = load_json(root / "bench" / "limits" / f"{name}.json")
    load_module("drivers", traffic["driver"], root / "bench")
    return Cell(name, entry, config, traffic, limits, bench, root)


def devices_for(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"[bench] platform={d.platform} device_kind={d.device_kind} "
          f"device_count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform is {d.platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


class Tracer:
    """Traces one stretch of the window: `start(seconds)` starts the
    profiler, and a thread of its own stops it ``seconds`` later; the
    traced window is that thread's host span `bench.traced`.  Without a
    directory it traces nothing."""

    def __init__(self, directory: str | None):
        self.dir = directory
        self._thread = None

    def start(self, seconds: float) -> None:
        if self.dir is None or self._thread is not None:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

        def stop_later():
            with jax.profiler.TraceAnnotation("bench.traced"):
                time.sleep(seconds)
            jax.profiler.stop_trace()

        self._thread = threading.Thread(target=stop_later, name="bench-tracer")
        self._thread.start()

    def wait(self) -> None:
        """Wait until the traced stretch has ended and the trace is written."""
        if self._thread is not None:
            self._thread.join()


@dataclass
class Ctx:
    """What a driver and a metric reader are given."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    counter: object = None
    tracer: Tracer = field(default_factory=lambda: Tracer(None))
    extra: dict = field(default_factory=dict)


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def measure(ctx: Ctx, t0: float = T0) -> dict:
    """One run of ``ctx.cell`` on ``ctx.devices``; returns the result object.
    Set-up is timed from ``t0``."""
    from bench.lib.monitor import Counter

    cell, devices = ctx.cell, ctx.devices
    ctx.counter = Counter()
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if ctx.trace else None
    ctx.tracer = Tracer(tdir)
    driver = load_module("drivers", cell.traffic["driver"], cell.root / "bench")
    try:
        state = driver.setup(ctx)
        setup_s = time.perf_counter() - t0
        before = ctx.counter.snapshot()
        record = driver.window(ctx, state)
        ctx.tracer.wait()
        comp = ctx.counter.delta(before, ctx.counter.snapshot())
        print(f"[bench] in the window: {comp['traced']} programs traced, "
              f"{comp['backend']} compile requests, {comp['cache_hits']} served "
              f"by the persistent cache, {comp['compiled']} compiled by XLA",
              flush=True)
        record["compiles_in_window"] = comp
        mem = memory_peak(devices)
        driver.release(state)
        del state
        gc.collect()
        result = {"correct": None, "attempted": record["attempted"],
                  "failed": record["failed"], "metrics": {}}
        if ctx.trace:
            from bench.lib import trace as tr

            t1 = time.perf_counter()
            t = tr.load(tdir)
            print(f"[bench] trace {tdir}: window {t.window_s:.3f} s, devices "
                  f"{sorted(t.ops)}, read in {time.perf_counter() - t1:.1f} s", flush=True)
            for m in cell.per_layer():
                v = load_module("metrics", m["name"], cell.root / "bench").read(ctx, record, t)
                if v is not None:
                    result["metrics"][m["name"]] = {"value": float(v), "unit": m["unit"]}
            busy = {"busy_s": tr.busy_s(t), "window_s": t.window_s}
            result["breakdown"] = {"device_ops": tr.top_ops(t),
                                   "idle_gaps": tr.idle_gaps(t)}
        else:
            values = driver.end_to_end(ctx, record)
            values["setup_s"] = setup_s
            for m in cell.end_to_end():
                result["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                                "unit": m["unit"]}
            busy = {}
        t1 = time.perf_counter()
        checks = driver.check(ctx, record)
        print(f"[bench] comparison with the reference took {time.perf_counter() - t1:.1f} s; "
              f"the run {time.perf_counter() - t0:.1f} s", flush=True)
    finally:
        ctx.tracer.wait()
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    d = devices[0]
    result["device"] = {"platform": d.platform, "kind": d.device_kind,
                        "count": len(devices), "memory_peak_bytes": mem, **busy}
    result["correct"] = bool(checks) and all(v <= lim for _, v, lim in checks)
    result["checks"] = {n: {"value": float(v), "limit": float(lim)} for n, v, lim in checks}
    return result


def open_cell(cell: Cell, seed: int, seconds: float, trace: bool) -> Ctx:
    """The run's context on this machine's chips, with the compilation
    cache on; raises `NoChip` where JAX finds no TPU or too few."""
    from repro.launch.compile_cache import enable_compile_cache

    devices = devices_for(cell.chips)
    print(f"[bench] compilation cache: {enable_compile_cache()}", flush=True)
    return Ctx(cell, seed, seconds, trace, devices, peaks_for(devices[0].device_kind))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}", file=sys.stderr)
        return 2
    cell = find_cell(args.workload)
    try:
        ctx = open_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    result = measure(ctx)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
