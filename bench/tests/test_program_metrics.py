"""The readers of the program's own spans and counters
(``decode_host_ms_p50``, ``prefill_stall_ms_p50``, ``kv_token_use``,
``setup_compile_s``) on a hand-made trace reduction and hand-made recorder
records, against hand-worked values; and on a program that records none of
them, as the parent of the change that added them (CPU)."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import core, trace  # noqa: E402

import repro  # noqa: E402
from repro import tracing  # noqa: E402

MS = 1_000_000  # ns
WINDOW = (100.0, 200.0)  # perf_counter seconds


def reader(name):
    return core.load_module(BENCH / "metrics" / f"{name}.chat.py").read


def reading(host=(), busy=(), window=WINDOW):
    red = trace.Reduction(ops={0: []}, busy={0: list(busy)},
                          spans=[], host=list(host), window_s=50.0)
    return trace.Reading(trace=red, counts={}, config={}, traffic={},
                         peaks={}, window=window, chips=1)


# four decode steps and three prefills, in ms on the device clock
HOST = [("serving.decode", 0, 20 * MS), ("serving.decode.run", 1 * MS, 2 * MS),
        ("serving.prefill", 21 * MS, 24 * MS),
        ("serving.prefill", 25 * MS, 33 * MS),
        ("serving.decode", 40 * MS, 50 * MS),
        ("serving.prefill", 52 * MS, 54 * MS),
        ("serving.decode", 60 * MS, 70 * MS),
        ("serving.decode", 80 * MS, 90 * MS)]
BUSY = [(2 * MS, 12 * MS), (15 * MS, 30 * MS), (40 * MS, 49 * MS),
        (80 * MS, 88 * MS)]


def test_decode_host_ms_p50():
    # idle inside each decode step: 20 - 10 - 5 = 5, 10 - 9 = 1, 10, 10 - 8
    # = 2 ms; the median of (1, 2, 5, 10)
    assert reader("decode_host_ms_p50")(reading(HOST, BUSY)) == 3.5


def test_prefill_stall_ms_p50():
    # the gap 20..50 ms holds 3 + 8 ms of prefill, 50..70 holds 2, 70..90
    # none: the median of (11, 2)
    assert reader("prefill_stall_ms_p50")(reading(HOST, BUSY)) == 6.5


def rec(name, t0, t1, **attrs):
    """A closed span as the recorder keeps it."""
    with tracing.Recorder().span(name, **attrs) as sp:
        pass
    sp.t0, sp.t1 = t0, t1
    return sp


RECORDS = [
    rec("serving.init", 1.0, 2.0, compile_s=1.5),
    rec("weights", 2.0, 20.0, compile_s=9.0),
    rec("serving.decode.run", 30.0, 40.0, compile_s=2.0),
    rec("serving.prefill.run", 41.0, 42.0, compile_s=0.25),
    rec("serving.decode", 90.0, 90.1, tokens_held=1, token_capacity=1000),
    rec("serving.decode", 120.0, 120.1, tokens_held=100, token_capacity=128),
    rec("serving.decode.run", 120.01, 120.05, compile_s=4.0),
    rec("serving.admit", 121.0, 121.1, admitted=1),
    rec("serving.decode", 130.0, 130.1, tokens_held=300, token_capacity=512),
]


@pytest.fixture
def recorded(monkeypatch):
    """The recorder's ``records`` made to hold ``RECORDS`` (and ``dropped``
    lost records before the window when set)."""
    state = {"dropped": 0}

    def records(since=None, until=None):
        out = tracing.Records(r for r in RECORDS
                              if (since is None or r.t0 >= since)
                              and (until is None or r.t1 <= until))
        out.dropped = state["dropped"]
        return out

    monkeypatch.setattr(tracing, "records", records)
    return state


def test_kv_token_use(recorded):
    # the window's two decode steps: (100 + 300) / (128 + 512)
    assert reader("kv_token_use")(reading()) == pytest.approx(62.5)
    recorded["dropped"] = 3
    assert reader("kv_token_use")(reading()) is None


def test_setup_compile_s(recorded):
    # serving spans that ended before the window: 1.5 + 2 + 0.25
    assert reader("setup_compile_s")(reading()) == pytest.approx(3.75)
    recorded["dropped"] = 1
    assert reader("setup_compile_s")(reading()) is None


NAMES = ["decode_host_ms_p50", "prefill_stall_ms_p50", "kv_token_use",
         "setup_compile_s"]


@pytest.mark.parametrize("name", NAMES)
def test_none_without_the_program_spans(name, monkeypatch):
    # the trace recorded on a v5e before the program had spans, and a
    # program without repro.tracing
    data = BENCH / "tests" / "data"
    meta = json.loads((data / "serve.json").read_text())
    red = trace.reduce_file(data / "serve.xplane.pb", set(meta["spans"]),
                            meta["window_s"])
    r = trace.Reading(trace=red, counts={}, config={}, traffic={}, peaks={},
                      window=WINDOW, chips=1)
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert reader(name)(r) is None


@pytest.mark.parametrize("name", NAMES)
def test_none_when_nothing_was_recorded(name, monkeypatch):
    monkeypatch.setattr(tracing, "records", lambda since=None, until=None:
                        tracing.Records())
    assert reader(name)(reading()) is None
