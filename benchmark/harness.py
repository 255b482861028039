"""What every cell's run shares: finding a cell's files by the names in
`BENCHMARK.json`, the look for a chip, the compile cache, the counters
read from JAX, the traced window, the per-layer readers and the result
line.  Nothing here belongs to one configuration, one traffic mix or one
metric: those are files of their own (see README.md).
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
TRACE_DIR = os.path.join(REPO, ".bench_trace")
CACHE_DIR = os.path.join(REPO, ".jax_cache")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py` as a module (a name may hold `-`)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{kind} {name!r} needs the file benchmark/{kind}/{name}.py")
    mod_name = f"bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, rehearse: bool = False):
    """(benchmark, cell, cfg): `BENCHMARK.json`, the cell's own file
    merged over its entry there, and its configuration's file.  With
    `rehearse` each file's `rehearsal` group overrides the real sizes."""
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in bench['workloads']]}")
    cell = dict(load_json(os.path.join(HERE, "workloads", f"{name}.json")))
    for k in ("name", "config", "chips"):
        if k in cell and cell[k] != entry[k]:
            raise SystemExit(f"benchmark/workloads/{name}.json says {k}="
                             f"{cell[k]!r}, BENCHMARK.json {entry[k]!r}")
        cell[k] = entry[k]
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cfg = dict(load_json(os.path.join(REPO, conf["file"])))
    if rehearse:
        cell.update(cell.get("rehearsal", {}))
        cfg.update(cfg.get("rehearsal", {}))
    return bench, cell, cfg


def cell_metrics(bench, cell_name, group):
    """The metrics of `group` (`end_to_end` / `per_layer`) that this cell
    reports: those with no `workloads` key whose end-to-end metric the
    cell reports, and those that list it."""
    e2e_here = {m["name"] for m in bench["end_to_end"]
                if "workloads" not in m or cell_name in m["workloads"]}
    if group == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in e2e_here]
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_here)]


# ------------------------------------------------------------------ device
def find_device(chips: int, rehearse: bool):
    """The look for a chip.  Anything but a TPU with at least `chips`
    devices is an error, unless this is the CPU rehearsal, which says so
    in every line it prints."""
    import jax

    if rehearse:
        jax.config.update("jax_platforms", "cpu")
    import deeplearning4j_tpu  # noqa: F401  (sets libtpu's compiler stack before the first jax.devices())
    devs = jax.devices()
    if rehearse:
        if len(devs) < chips:
            raise SystemExit(f"rehearsal needs {chips} virtual CPU devices "
                             f"(XLA_FLAGS=--xla_force_host_platform_device_"
                             f"count={chips}), jax has {len(devs)}")
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU; jax found platform "
                         f"{devs[0].platform!r} ({devs[0].device_kind}). "
                         f"Nothing was run.")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, jax "
                         f"found {len(devs)}. Nothing was run.")
    return devs[:chips]


def peaks_of(devs, args):
    """The chip's published peaks; None in the CPU rehearsal, where no
    share of a peak is ever reported; an unknown chip is an error."""
    import flops

    if args.rehearse_cpu:
        return None
    return flops.device_peaks(devs[0].device_kind)


def bytes_in_use(devs) -> int:
    """What the fullest chip's allocator holds in live arrays now."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devs)


def device_line(devs, window_bytes=()):
    """The device as JAX reports it.  `memory_peak_bytes` is the fullest
    chip's `peak_bytes_in_use` as the allocator gives it at the window's
    close: the process's peak of live arrays, set-up included, which
    cannot pass the chip.  Two readings are printed beside it and added
    to nothing: `memory_window_bytes`, the most that `bytes_in_use` read
    at the samples taken inside the window (what the deployment holds
    once it is warm), and `memory_reserved_peak_bytes`, the allocator's
    `peak_bytes_reserved`, under which this backend counts the region a
    program reserves for its temporaries (seen on the chip, PR 26: the
    GPT-2 step reserves 10.92 GB where the compiler's own figure for its
    temporaries is 10.96 GB)."""
    best = {"memory_peak_bytes": -1}
    for d in devs:
        st = d.memory_stats() or {}
        used = int(st.get("peak_bytes_in_use", 0))
        if used > best["memory_peak_bytes"]:
            best = {"memory_peak_bytes": used,
                    "memory_reserved_peak_bytes":
                        int(st.get("peak_bytes_reserved", 0))}
    if window_bytes:
        best["memory_window_bytes"] = int(max(window_bytes))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), **best}


# ---------------------------------------------------------------- counters
class Counters:
    """Compiles and persistent-cache events, from `jax.monitoring`."""

    def __init__(self):
        import jax

        from deeplearning4j_tpu.monitor import (JitCompileCollector,
                                                MetricsRegistry)
        from deeplearning4j_tpu.nd import enable_compilation_cache

        # the benchmark gives the cache its place: one fixed directory inside
        # the checkout, whatever the environment names (a directory of the
        # machine's would be shared by a parent and a change checked side by
        # side), and no size limit from the environment may evict from it (a
        # serving cell's grid is some 80 programs; under a 192 MiB limit
        # every run of it compiled anew, my chip runs, PR 26)
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_compilation_cache_max_size", -1)
        self.cache_dir = enable_compilation_cache(min_compile_time_secs=0.0)
        self._coll = JitCompileCollector(MetricsRegistry()).install()
        self.cache_requests = self.cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def compiles(self) -> int:
        return int(self._coll.compile_count())

    def compile_seconds(self) -> float:
        return float(self._coll.compile_seconds())


class PeakWatch(threading.Thread):
    """Where in set-up the allocator's peak was set: samples the live
    bytes every 20 ms and keeps the largest with the number of programs
    compiled or loaded by then.  A log line only."""

    def __init__(self, devs, counters):
        super().__init__(daemon=True, name="bench-peak-watch")
        self.devs, self.counters = devs, counters
        self.halt = threading.Event()
        self.t0 = time.monotonic()
        self.most = (0, 0, 0.0)     # bytes, programs so far, seconds in

    def run(self):
        while not self.halt.wait(0.02):
            b = bytes_in_use(self.devs)
            if b > self.most[0]:
                self.most = (b, self.counters.compiles(),
                             time.monotonic() - self.t0)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.halt.set()
        self.join()


# ------------------------------------------------------------------- trace
class Tracer:
    """The profiler round the first `seconds` of the measured window, with
    the host annotation the reduction looks for.  `on` False: every call
    is a no-op, and `span` costs one `if`."""

    def __init__(self, on: bool, seconds: float = 3.0, host_level: int = 1):
        self.on, self.seconds, self.host_level = on, seconds, host_level
        self.running = False
        self._closer = None
        self.t_start = self.t_stop = None
        self._win = None
        self.reduced = None

    def start(self):
        if not self.on:
            return
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = self.host_level   # 1: TraceAnnotation spans
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        from trace_reduce import WINDOW
        self._win = jax.profiler.TraceAnnotation(WINDOW)
        self._win.__enter__()
        self.running = True
        self.t_start = time.monotonic()

    def due(self) -> bool:
        return self.running and time.monotonic() - self.t_start >= self.seconds

    def stop(self, background=False):
        """Close the window's annotation (on the thread that opened it)
        and the profile.  `background`: close the profile on a helper
        thread, where closing it here would make a load generator late;
        `reduce` waits for it."""
        if not self.running:
            return
        import jax

        self.running = False
        self.t_stop = time.monotonic()
        self._win.__exit__(None, None, None)
        if background:
            self._closer = threading.Thread(target=jax.profiler.stop_trace)
            self._closer.start()
        else:
            jax.profiler.stop_trace()

    def span(self, name):
        import contextlib

        if not self.running:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def reduce(self):
        if not self.on or self.t_stop is None:
            return None
        import trace_reduce

        if self._closer is not None:
            self._closer.join()

        files = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if files:
            self.reduced = trace_reduce.reduce(files[0])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return self.reduced


# ----------------------------------------------------------------- readers
def read_layer_metrics(bench, cell, ctx):
    """Every per-layer metric of this cell through its reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell_metrics(bench, cell["name"], "per_layer"):
        spec = load_json(os.path.join(HERE, "layer_metrics",
                                      f"{m['name']}.json"))
        reader = load_module("readers", spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ result
def say(msg):
    print(f"[bench +{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


T0 = time.monotonic()


def print_result(result, compared):
    """The contract's last lines: the numbers compared, each beside its
    limit, on standard error, and the one JSON object on standard output
    with `compared` as its last key."""
    lines = [f"compared {k}: value={v['value']!r} limit={v['limit']!r} "
             f"{'ok' if v['ok'] else 'FAILED'}" for k, v in compared.items()]
    result = dict(result)
    result["compared"] = compared
    sys.stdout.flush()
    print("\n".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
