"""Plumbing shared by every cell: the benchmark file, files found by name,
the device, the compile cache, compile time, host spans and the result line.

Nothing here knows a configuration, a traffic mix or a metric: those live in
files of their own (``bench/configs``, ``bench/traffic``, ``bench/drivers``,
``bench/metrics``, ``bench/roofline``) and are found by the names that
``BENCHMARK.json`` gives.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import sys
import time
from typing import Any, Optional

BENCH = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
OUT = CHECKOUT / ".bench_out"


class Refused(Exception):
    """The run cannot measure what it was asked to: no result is printed."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: pathlib.Path) -> Any:
    return json.loads(path.read_text())


def load_module(path: pathlib.Path):
    """Import a file found by name (names hold dashes and dots, so they are
    loaded by path, not by package)."""
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(CHECKOUT)}")
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise Refused(f"no workload {workload!r} in BENCHMARK.json")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise Refused(f"no configuration {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The end-to-end (``kind="end_to_end"``) or per-layer metrics that this
    cell reports: those whose ``workloads`` list names it, or that have no
    list."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# device


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "roofline" / "peaks.json")
    if kind not in table["devices"]:
        raise Refused(f"device kind {kind!r} is not in bench/roofline/"
                      f"peaks.json; add its published peaks there")
    return table["devices"][kind]


def require_device(chips: int):
    """The devices the cell runs on.  No TPU, too few chips, or Pallas in
    interpret mode: refused, since the numbers would not be the chip's."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devices)}")
    from repro.kernels._backend import should_interpret

    if should_interpret():
        raise Refused("Pallas kernels would run in interpret mode")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``.jax_cache``), or where ``JAX_COMPILATION_CACHE_DIR`` says.  Every
    program is cached, however short its compile, so a second run of a
    cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip since the process started."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileMeter:
    """Seconds of backend compilation (XLA and Mosaic), and how many
    compiles and persistent-cache hits there were.  A cache hit counts its
    retrieval time.  A compile inside the measured window is a fault of the
    warm-up."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.seconds, self.compiles, self.cache_hits

    def since(self, mark):
        return (self.seconds - mark[0], self.compiles - mark[1],
                self.cache_hits - mark[2])


class Spans:
    """Host spans around each call into the program.  Each span is written
    into the profiler's trace (``TraceAnnotation``), so the trace reduction
    gives device time to the span it falls in, and kept here with its host
    clock times."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    def __call__(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, spans: Spans, name: str):
        import jax

        self.spans, self.name = spans, name
        self.ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.spans.records.append((self.name, self.t0, time.perf_counter()))
        self.ann.__exit__(*exc)
        return False


@dataclasses.dataclass
class Check:
    """One number compared with its limit.  ``op`` is "<=" (the number may
    not exceed the limit), ">=" (a count that must be reached) or "=="
    (exact)."""

    name: str
    value: float
    limit: float
    op: str = "<="

    @property
    def ok(self) -> bool:
        if self.value != self.value:  # NaN never passes
            return False
        if self.op == "==":
            return self.value == self.limit
        if self.op == ">=":
            return self.value >= self.limit
        return self.value <= self.limit

    def as_json(self) -> dict:
        return {"value": self.value, "limit": self.limit, "op": self.op,
                "ok": self.ok}


@dataclasses.dataclass
class Outcome:
    """What a driver hands back: the counts for ``attempted`` and
    ``failed``, the end-to-end readings by metric name, the comparisons that
    decide ``correct``, and what the per-layer readers read (``counts``, by
    name, filled in the window)."""

    attempted: int
    failed: int
    end_to_end: dict[str, float]
    checks: list[Check]
    counts: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


@dataclasses.dataclass
class Context:
    """Everything a driver needs for one run of one cell."""

    args: Any
    bench: dict
    cell: dict
    config: dict           # bench/configs/<config>.json
    traffic: dict          # bench/traffic/<cell>.json
    devices: list
    meter: CompileMeter
    spans: Spans
    t_start: float         # perf_counter at process start
    trace_dir: Optional[pathlib.Path] = None
    setup_s: Optional[float] = None
    window: Optional[tuple[float, float]] = None  # perf_counter open, close

    def open_window(self) -> float:
        """Set-up ends here: compilation, warm-up and any prelude the
        traffic needs are behind.  Starts the trace in a traced run."""
        if self.trace_dir is not None:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        t = time.perf_counter()
        self.setup_s = t - self.t_start
        self.window_mark = self.meter.mark()
        self._open = t
        return t

    def close_window(self) -> float:
        t = time.perf_counter()
        self.window = (self._open, t)
        if self.trace_dir is not None:
            import jax

            jax.profiler.stop_trace()
        secs, n, _ = self.meter.since(self.window_mark)
        self.window_compiles = n
        if n:
            log(f"{n} compiles ({secs:.3f} s) inside the window")
        return t
