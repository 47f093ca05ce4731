"""One run of one cell: find its files, check the device, hand over to the
cell's driver, read the metrics the manifest lists, print the result."""

import argparse
import os
import sys
import time
import types

from . import result
from .loader import Benchmark, BenchmarkError, check_unit
from .peaks import peaks_for
from .trace import Tracer

SETUP = "setup_s"  # the one metric the harness takes itself


def _args(argv):
    ap = argparse.ArgumentParser(prog="perf/run.py")
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json, or the path of a cell "
                    "file (rehearsals)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="measured window; default: BENCHMARK.json's "
                    "run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the cell's control (the reference in a lower "
                    "precision in the program's place) instead of the "
                    "program; prints what the comparison reads and no result")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes from the config's 'rehearse' block on "
                    "any backend; the output is marked and carries no value")
    return ap.parse_args(argv)


class CompileCounter:
    """Counts XLA compilations (and loads from the persistent cache) through
    ``jax.monitoring``: inside the measured window there must be none. It
    also adds up, by the event's own name, the seconds JAX reports for
    tracing, lowering, compiling and reading the cache, so that a long
    set-up says where it went."""

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.seconds = {}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        name = event.rsplit("/", 1)[-1]
        self.seconds[name] = self.seconds.get(name, 0.0) + duration
        # a fresh compilation, or a program loaded from the persistent cache
        if event.endswith(("backend_compile_duration",
                           "cache_retrieval_time_sec")):
            self.count += 1

    def _event(self, event, **kw):
        if event.endswith("/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("/cache_misses"):
            self.cache_misses += 1


def _device(jax, chips, rehearse):
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rehearse:
        return dev, None
    if dev["platform"] != "tpu":
        raise BenchmarkError(
            f"no accelerator: jax.devices()[0].platform is "
            f"{dev['platform']!r}; a CPU timing is never recorded as a "
            "device metric (use --rehearse off the chip)")
    if dev["count"] < chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chips, jax sees {dev['count']}")
    return dev, peaks_for(dev["kind"])


class Memory:
    """The device allocator's counters on the fullest chip, read at single
    instants. On the TPU the allocator keeps two accounts: arrays
    (``bytes_in_use``) and what loaded programs hold for their temporaries
    (``bytes_reserved``); a BERT-base step that reserves 3.9 GB leaves
    ``peak_bytes_in_use`` at 1.4 GB (my chip runs, PR 24). A driver calls
    ``sample`` while its window's programs are loaded and its state is
    live; each sample goes on an earlier line under the counters' own
    names, and the two accounts are added only within one sample."""

    def __init__(self, jax, chips, say):
        self.devices, self.say = jax.devices()[:chips], say
        self.held = 0

    def _fullest(self):
        stats = [d.memory_stats() or {} for d in self.devices]
        return max(stats, key=lambda s: s.get("bytes_in_use", 0)
                   + s.get("bytes_reserved", 0))

    def sample(self, at):
        s = self._fullest()
        held = int(s.get("bytes_in_use", 0)) + int(s.get("bytes_reserved", 0))
        self.held = max(self.held, held)
        self.say("memory", at=at, held_bytes=held,
                 **{k: v for k, v in s.items() if isinstance(v, int)})

    def peak(self):
        """``memory_peak_bytes``: the most that one sample saw held, or the
        allocator's own peak of arrays where that is more."""
        return max(self.held,
                   int(self._fullest().get("peak_bytes_in_use", 0)))


def main(argv, process_start, root):
    args = _args(argv)
    bench = Benchmark(root)
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    seconds = args.seconds if args.seconds is not None \
        else float(bench.manifest["run_seconds"])
    chips = int(cell["chips"])
    if args.rehearse:
        if "rehearse" not in config:
            raise BenchmarkError(
                f"configuration {config['name']!r} has no 'rehearse' block")
        tiny = config["rehearse"]
        config = dict(config, **tiny.get("config", {}))
        traffic = dict(traffic, **tiny.get("traffic", {}))
        if chips > 1:
            # virtual CPU devices where the rehearsal has no chips; in
            # place before jax starts its backends
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={chips}").strip()
    # every program goes to the persistent cache, the small ones too, so
    # that only a checkout's first run of a cell compiles. The directory is
    # the program's own fixed one inside the checkout, or the one
    # JAX_COMPILATION_CACHE_DIR names
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

    import jax

    device, peaks = _device(jax, chips, args.rehearse)
    result.say("device", **device, jax=jax.__version__,
               cell=cell["name"], config=config["name"],
               traffic=traffic["name"], seed=args.seed, seconds=seconds,
               trace=args.trace, rehearse=args.rehearse)
    compiles = CompileCounter()
    tracer = Tracer(bool(args.trace),
                    os.path.join(root, ".mxtpu_cache", "perf_trace",
                                 cell["name"]), chips)
    ctx = types.SimpleNamespace(
        bench=bench, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=seconds, trace=bool(args.trace),
        rehearse=args.rehearse, chips=chips, root=root,
        process_start=process_start, tracer=tracer, compiles=compiles,
        peaks=peaks, device=device, say=result.say,
        memory=Memory(jax, chips, result.say))
    driver = bench.driver(config["driver"])
    if args.control:
        failed = driver.control(ctx)
        result.say("control", seed=args.seed, found_not_correct=failed)
        return 0 if failed else 4
    run = driver.run(ctx)

    run.setup_s = run.window_start - process_start - run.reference_s_in_setup
    run.trace = tracer.reduce()
    run.ctx = ctx
    result.say("setup", setup_s=run.setup_s,
               reference_s_not_counted=run.reference_s_in_setup,
               compiles_before_window=run.compiles_before_window,
               compiles_in_window=run.compiles_in_window,
               cache_hits=compiles.cache_hits,
               cache_misses=compiles.cache_misses,
               jax_seconds=compiles.seconds)
    if run.compiles_in_window:
        run.correct = False
        result.say("incorrect", why="a program compiled inside the window",
                   count=run.compiles_in_window)

    metrics = {}
    if args.trace:
        for name, unit in bench.per_layer(cell):
            reader = bench.layer_metric(name)
            value = reader.read(run)
            if value is None:
                continue  # nothing to read here: left out of the line
            if unit is not None and unit != reader.UNIT:
                raise BenchmarkError(
                    f"per-layer metric {name!r}: BENCHMARK.json says "
                    f"{unit!r}, its reader says {reader.UNIT!r}")
            metrics[name] = (float(value), check_unit(reader.UNIT))
    else:
        measured = dict(run.e2e)
        measured[SETUP] = (run.setup_s, "s")
        listed = bench.end_to_end(cell)
        if listed is None:
            metrics = measured
        else:
            for m in listed:
                if m["name"] not in measured:
                    raise BenchmarkError(
                        f"cell {cell['name']!r} lists {m['name']!r} but "
                        f"its driver did not measure it")
                value, unit = measured[m["name"]]
                if unit != m["unit"]:
                    raise BenchmarkError(
                        f"{m['name']!r}: BENCHMARK.json says {m['unit']!r}, "
                        f"the driver says {unit!r}")
                metrics[m["name"]] = (float(value), unit)

    device["memory_peak_bytes"] = ctx.memory.peak()
    breakdown = None
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
        if not args.rehearse and not run.trace.busy_s > 0:
            raise BenchmarkError("the traced window shows no operation on "
                                 "the device")
    # every process the run started has ended by now (drivers join their
    # threads); the line below is the last thing written
    result.emit(result.result_line(
        correct=run.correct, attempted=run.attempted, failed=run.failed,
        metrics=metrics, device=device, breakdown=breakdown,
        rehearsal=args.rehearse))
    sys.stdout.flush()
    return 0


class Run:
    """What a driver hands back."""

    def __init__(self):
        self.correct = False
        self.attempted = 0
        self.failed = 0
        self.e2e = {}            # name -> (value, unit), without setup_s
        self.obs = {}            # counts and samples for per-layer readers
        self.window_start = time.perf_counter()
        self.window_s = 0.0
        self.reference_s_in_setup = 0.0
        self.compiles_before_window = 0
        self.compiles_in_window = 0
        self.setup_s = None
        self.trace = None
        self.ctx = None
