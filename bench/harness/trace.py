"""Reduce a profiler trace of the measured window to what the per-layer
readers need.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
What a TPU trace holds (looked at by hand on a v5e):

* one plane per chip, ``/device:TPU:<n>``, with a line ``XLA Ops`` whose
  events are the HLO instructions that ran, named by their HLO text
  (``%_paged_decode.9 = (...) custom-call(...), custom_call_target=
  "tpu_custom_call"``), and a line ``XLA Modules`` with one event per
  executed program;
* the host plane ``/host:CPU``, whose line for the Python main thread
  (named after the interpreter, ``python3``) holds the driver's spans
  (``TraceAnnotation``: ``prefill``, ``decode``, ``train_step``) and JAX's
  own host events, on the same clock as the device lines.

An op is named by the part of its HLO name before the instance number:
``_paged_decode`` for ``%_paged_decode.9``.  Pallas kernels are the
custom calls with ``tpu_custom_call`` in their text; they carry the name of
the jitted function that wraps the ``pallas_call``.  Loop and call ops
(``while``, ``conditional``, ``call``) contain the ops of their bodies and
are left out of per-op sums; the busy time is the union of all op intervals.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import pathlib
import re
from collections import defaultdict

_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def op_name(text: str) -> str:
    """``%_paged_decode.9 = ...`` -> ``_paged_decode``."""
    m = _NAME.match(text)
    return m.group(1) if m else text.split(" ")[0].lstrip("%")


def is_collective(name: str) -> bool:
    return any(name.startswith(c) for c in COLLECTIVES)


def union(intervals) -> list[tuple[int, int]]:
    """Merge [start, end) intervals (nanoseconds)."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Op:
    name: str       # short name
    kernel: bool    # a Pallas (Mosaic) kernel
    start: int      # ns
    end: int        # ns


@dataclasses.dataclass
class Reduction:
    """Device time by chip, op and host span."""

    ops: dict[int, list[Op]]              # chip -> ops (no containers)
    busy: dict[int, list[tuple[int, int]]]   # chip -> merged busy intervals
    spans: list[tuple[str, int, int]]     # driver's host spans
    host: list[tuple[str, int, int]]      # other host events, main thread
    window_s: float

    @property
    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the chips."""
        return sum(total(b) for b in self.busy.values()) / 1e9 / max(
            len(self.busy), 1)

    def span_of(self, t: int):
        """The driver's span at ``t`` (spans do not overlap)."""
        if not hasattr(self, "_starts"):
            self._starts = [s for _, s, _ in self.spans]
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and t < self.spans[i][2]:
            return self.spans[i][0]
        return None

    def op_seconds(self, name: str, span: str | None = None,
                   chip: int = 0) -> float:
        """Device seconds of the ops named ``name`` on ``chip``, only those
        that ran inside a host span named ``span`` when given."""
        ns = 0
        for op in self.ops.get(chip, []):
            if op.name != name:
                continue
            if span is not None and self.span_of((op.start + op.end) // 2) != span:
                continue
            ns += op.end - op.start
        return ns / 1e9

    def span_busy_s(self, span: str, chip: int = 0) -> float:
        """Device busy seconds on ``chip`` inside host spans ``span``."""
        own = union((s, e) for n, s, e in self.spans if n == span)
        busy = self.busy.get(chip, [])
        return (total(busy) - total(subtract(busy, own))) / 1e9

    def exposed_collective_s(self, chip: int = 0) -> float:
        """Seconds in which a collective ran on ``chip`` and nothing else."""
        ops = self.ops.get(chip, [])
        coll = union((o.start, o.end) for o in ops if is_collective(o.name))
        comp = union((o.start, o.end) for o in ops
                     if not is_collective(o.name))
        return total(subtract(coll, comp)) / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time on chip 0, by span and name,
        and the idle time by what the host was doing."""
        by_op: dict[str, int] = defaultdict(int)
        for op in self.ops.get(0, []):
            span = self.span_of((op.start + op.end) // 2) or "-"
            by_op[f"{span}/{op.name}"] += op.end - op.start
        idle: dict[str, int] = defaultdict(int)
        busy = self.busy.get(0, [])
        if busy:
            lo, hi = self._extent()
            gaps = subtract([(lo, hi)], busy)
            for s, e in gaps:
                idle[self._host_label((s + e) // 2)] += e - s
        rank = lambda d: [[k, v / 1e9] for k, v in sorted(  # noqa: E731
            d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(by_op), "idle_gaps": rank(idle)}

    def _extent(self) -> tuple[int, int]:
        """The traced window: from the first to the last host span or op."""
        starts = [s for _, s, _ in self.spans]
        ends = [e for _, _, e in self.spans]
        for b in self.busy.values():
            if b:
                starts.append(b[0][0])
                ends.append(b[-1][1])
        return min(starts), max(ends)

    def _host_label(self, t: int) -> str:
        span = self.span_of(t) or "between spans"
        inner = [(e - s, n) for n, s, e in self.host if s <= t < e]
        return f"{span}/{min(inner)[1]}" if inner else span


def _xplane(trace_dir) -> pathlib.Path:
    found = sorted(glob.glob(str(pathlib.Path(trace_dir) / "**" /
                                 "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return pathlib.Path(found[-1])


def reduce_file(path, span_names, window_s: float) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: dict[int, list[Op]] = {}
    busy: dict[int, list[tuple[int, int]]] = {}
    spans, host = [], []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            chip = int(m.group(1))
            ivs, mine = [], []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    ivs.append((s, e))
                    name = op_name(ev.name)
                    if name in CONTAINERS:
                        continue
                    mine.append(Op(name, "tpu_custom_call" in ev.name, s, e))
            ops[chip] = mine
            busy[chip] = union(ivs)
        elif plane.name == "/host:CPU":
            # the driver's spans are on the Python main thread's line (named
            # after the interpreter: "python", "python3"); JAX's own host
            # events on that line say what the host was doing
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    rec = (ev.name, s, s + int(ev.duration_ns))
                    (spans if ev.name in span_names else host).append(rec)
    spans.sort(key=lambda r: r[1])
    return Reduction(ops=ops, busy=busy, spans=spans, host=host,
                     window_s=window_s)


def reduce_dir(trace_dir, span_records, window, n_devices: int) -> Reduction:
    """Reduce the newest trace under ``trace_dir``.  ``span_records`` are
    the driver's (name, start, end) host spans; their names pick the span
    events out of the host plane."""
    names = {n for n, _, _ in span_records}
    red = reduce_file(_xplane(trace_dir), names, window[1] - window[0])
    # keep the chips the cell ran on
    red.ops = {c: v for c, v in red.ops.items() if c < n_devices}
    red.busy = {c: v for c, v in red.busy.items() if c < n_devices}
    return red


@dataclasses.dataclass
class Reading:
    """What a per-layer reader is handed: the reduced trace, the driver's
    counts from the window, the configuration, traffic and peaks."""

    trace: Reduction
    counts: dict
    config: dict
    traffic: dict
    peaks: dict
    window: tuple
    chips: int
