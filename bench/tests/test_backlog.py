"""The backlog cell (``olmoe-1b-7b.decode-backlog-tp4``) at a size the CPU
holds: the driver, the program's sharded engine and the plain reference,
with the device check skipped.

* an unbroken run is correct, on one device and on a (1, 4) mesh of four
  host devices, and its routing counters hold every routed row; a layout
  too short for the rows routed reads ``correct`` false;
* a token altered where it is produced reads ``correct`` false;
* the fp8 control, put in the program's place, misses the gap limit by a
  wide margin;
* the cell's readers give hand-worked values on hand-made records
  and traces, and None where the program records no routing counters.
"""
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

import tiny
from harness import core, serving, trace

import repro  # noqa: F401
from repro import tracing

CELL = "olmoe-1b-7b.decode-backlog-tp4"
REFERENCE = core.load_module(tiny.BENCH / "references" / "olmoe_decoder.py")


def _config(**size):
    cfg = tiny.config("olmoe-1b-7b", intermediate_size=64, num_experts=8,
                      num_experts_per_tok=2, **size)
    cfg["program"]["settings"].update(moe_d_ff=cfg["intermediate_size"],
                                      n_experts=8, n_active_experts=2)
    return cfg


def _backlog(seed=3, model=1, seconds=2.0):
    tr = tiny.traffic(
        CELL, prompt={"median": 24, "min": 8, "max": 60},
        output={"median": 8, "min": 4, "max": 16}, backlog=4, stream=200,
        prelude_s=1.0, mesh={"data": 1, "model": model},
        engine={"max_slots": 4, "page_size": 16, "max_context": 96,
                "num_pages": 40},
        # the cell's gap limit is set from readings at its own size on the
        # chip (program 0.055 at most); at this width of 64 the PWL tables
        # move the flatter logits further (up to 0.23), so the runs here
        # hold the program to 0.5
        check={"served_tokens": 40, "min_tokens": 20, "gap_limit": 0.5})
    return tiny.context(CELL, _config(), tr, seed, seconds, chips=model)


def test_unbroken_backlog_run_is_correct():
    out = tiny.driver("serve_backlog").run(_backlog())
    assert out.correct, out.checks
    assert out.attempted > 0 and out.failed == 0
    assert out.end_to_end["itl_p95_ms"] > 0
    assert {c.name for c in out.checks} == {
        "served_gap_sd_max", "served_tokens_compared", "routed_rows_dropped"}


def test_traced_run_keeps_to_the_trace_window():
    # a traced run reads per-layer metrics only, over a window of the
    # traffic's trace_window_s when --seconds is longer
    # (--seconds 6 leaves room for a slow step on a loaded host and still
    # tells the two windows apart)
    ctx = _backlog(seconds=6.0)
    ctx.args.trace = 1
    ctx.traffic["trace_window_s"] = 1.0
    out = tiny.driver("serve_backlog").run(ctx)
    assert out.correct, out.checks
    lo, hi = ctx.window
    assert 1.0 <= hi - lo < 3.0


def test_backlog_token_altered(monkeypatch):
    from repro.serving.engine import PagedServingEngine

    orig = PagedServingEngine._exec

    def broken(self, phase, args):
        logits, cache = orig(self, phase, args)
        return (logits.at[..., 7].add(1e3) if phase == "decode"
                else logits), cache

    monkeypatch.setattr(PagedServingEngine, "_exec", broken)
    assert not tiny.driver("serve_backlog").run(_backlog()).correct


def test_undersized_layout_reads_rows_dropped(monkeypatch):
    # a grouped layout one row tile long loses the rows of every group past
    # the first: the routing check counts the steps that lost rows
    from repro.models import moe

    monkeypatch.setattr(moe, "layout_rows", lambda T, K, E, bm: bm)
    out = tiny.driver("serve_backlog").run(_backlog())
    dropped = {c.name: c for c in out.checks}["routed_rows_dropped"]
    assert dropped.value > 0 and not dropped.ok
    assert not out.correct


def test_backlog_on_four_host_devices():
    """The cell's own mesh shape, (1, 4): two experts and one head a rank;
    every decode record splits its rows over four ranks, which add up to
    the rows routed."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(tiny.BENCH / 'tests')!r})
        import test_backlog as t
        from repro import tracing
        out = t.tiny.driver("serve_backlog").run(t._backlog(model=4))
        recs = [r.attrs for r in tracing.records()
                if r.name == "serving.decode" and "rank_rows" in r.attrs]
        assert recs and all(len(a["rank_rows"]) == 4 for a in recs)
        assert all(sum(a["rank_rows"]) == a["routed_rows"] for a in recs)
        assert out.correct, out.checks
        print("OK", len(recs))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(tiny.BENCH.parent / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


# four layers at width 256: wide and deep enough for float8 rounding to
# move the top token by more than the limit, small enough for the CPU
SIZE = {"num_hidden_layers": 4, "hidden_size": 256, "vocab_size": 4096,
        "num_attention_heads": 2, "num_key_value_heads": 2}


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_backlog_control_fails_the_limit(seed):
    import jax

    from repro.distributed.sharding import make_rules
    from repro.launch.mesh import make_mesh
    from repro.models import Model

    cfg = _config(**SIZE)
    drv = tiny.driver("serve_backlog")
    prog = drv.program_config(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    params = drv.make_params(Model(prog), make_rules(prog, mesh), seed)
    r = np.random.default_rng(seed)
    seqs = [types.SimpleNamespace(prompt=r.integers(1, 4096, 100).tolist(),
                                  tokens=r.integers(1, 4096, 200).tolist())
            for _ in range(3)]
    gaps = serving.served_gaps(REFERENCE, cfg, params, seqs, "fp8",
                               pick="control")
    limit = tiny.traffic(CELL)["check"]["gap_limit"]
    assert gaps.size == 600
    assert gaps.max() > 5 * limit


# -- the readers --------------------------------------------------------------

MS = 1_000_000  # ns
WINDOW = (100.0, 200.0)  # perf_counter seconds
CONFIG = {"hidden_size": 2048, "intermediate_size": 1024, "dtype": "bfloat16",
          "num_hidden_layers": 16, "num_experts": 64}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return core.load_module(tiny.BENCH / "metrics" / f"{name}.backlog.py").read


def rec(name, t0, t1, **attrs):
    with tracing.Recorder().span(name, **attrs) as sp:
        pass
    sp.t0, sp.t1 = t0, t1
    return sp


# two decode steps in the window (one before it), and a prefill
RECORDS = [
    rec("serving.decode", 90.0, 90.1, routed_rows=8192, expert_rows_max=99,
        rank_rows=[2048] * 4, rank_experts=[256] * 4),
    rec("serving.decode", 120.0, 120.1, routed_rows=8192, expert_rows_max=16,
        rank_rows=[2000, 2100, 2092, 2000], rank_experts=[250, 256, 256, 256]),
    rec("serving.prefill", 121.0, 121.2, routed_rows=16384,
        expert_rows_max=400, rank_rows=[4096] * 4, rank_experts=[256] * 4),
    rec("serving.decode", 130.0, 130.1, routed_rows=8192, expert_rows_max=24,
        rank_rows=[1900, 2200, 2092, 2000], rank_experts=[240, 256, 256, 256]),
]


@pytest.fixture
def recorded(monkeypatch):
    state = {"dropped": 0, "records": RECORDS}

    def records(since=None, until=None):
        out = tracing.Records(r for r in state["records"]
                              if (since is None or r.t0 >= since)
                              and (until is None or r.t1 <= until))
        out.dropped = state["dropped"]
        return out

    monkeypatch.setattr(tracing, "records", records)
    return state


def reading(ops=(), busy=(), spans=()):
    red = trace.Reduction(ops={0: list(ops)}, busy={0: list(busy)},
                          spans=list(spans), host=[], window_s=100.0)
    return trace.Reading(trace=red, counts={}, config=CONFIG, traffic={},
                         peaks=PEAKS, window=WINDOW, chips=4)


def test_expert_rows_max(recorded):
    # an even load is 8192 / (16 * 64) = 8 rows; the window's two decode
    # steps stand 16 / 8 and 24 / 8 above it
    assert reader("expert_rows_max")(reading()) == pytest.approx(2.5)
    recorded["dropped"] = 1
    assert reader("expert_rows_max")(reading()) is None


def test_moe_collective_share():
    # busy 0..20 ms; an all-reduce 8..14 ms of which 10..12 ms has nothing
    # else running: 2 of 20 ms
    ops = [trace.Op("fusion", False, 0, 10 * MS),
           trace.Op("all-reduce", False, 8 * MS, 14 * MS),
           trace.Op("moe_glu", True, 12 * MS, 20 * MS)]
    busy = trace.union((o.start, o.end) for o in ops)
    got = reader("moe_collective_share")(reading(ops, busy))
    assert got == pytest.approx(100.0 * 2 / 20)
    assert reader("moe_collective_share")(reading()) is None


def test_none_without_routing_counters(recorded):
    # a dense program's decode records
    recorded["records"] = [rec("serving.decode", 120.0, 120.1, active=3)]
    assert reader("expert_rows_max")(reading()) is None


@pytest.mark.parametrize("name", ["expert_rows_max", "setup_compile_s"])
def test_none_without_the_program_tracing(name, monkeypatch):
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert reader(name)(reading()) is None


# -- the readers this cell shares with the chat cell ---------------------------


def test_paged_decode_roofline_counts_one_chips_heads():
    # two decode steps of 16 layers each, three and two slots deep; chip 0
    # holds 16 / 4 = 4 query and 4 KV heads of 128; 8 ms of _paged_decode in
    # the decode span, 3 ms in a prefill span (not counted)
    cfg = dict(CONFIG, num_attention_heads=16, num_key_value_heads=16)
    depths = [[100, 200, 300], [50, 60]]
    ops = [trace.Op("_paged_decode", True, 1 * MS, 9 * MS),
           trace.Op("_paged_decode", True, 30 * MS, 33 * MS)]
    spans = [("decode", 0, 20 * MS), ("prefill", 25 * MS, 40 * MS)]
    red = trace.Reduction(ops={0: ops}, busy={0: []}, spans=spans, host=[],
                          window_s=100.0)
    r = trace.Reading(trace=red, counts={"decode": depths}, config=cfg,
                      traffic={"mesh": {"data": 1, "model": 4}}, peaks=PEAKS,
                      window=WINDOW, chips=4)
    least = 0.0
    for d in depths:
        live = sum(d)
        flops = 4.0 * 4 * 128 * live
        nbytes = 2.0 * 4 * 128 * live * 2 + 2.0 * len(d) * 4 * 128 * 2
        least += 16 * max(flops / 197e12, nbytes / 819e9)
    assert reader("paged_decode_roofline")(r) == pytest.approx(
        100.0 * least / 0.008)
    r.counts = {}
    assert reader("paged_decode_roofline")(r) is None


def _host_reading(host, busy, window_s=0.1):
    red = trace.Reduction(ops={0: [], 1: []}, busy={0: busy, 1: busy},
                          spans=[], host=host, window_s=window_s)
    return trace.Reading(trace=red, counts={}, config=CONFIG, traffic={},
                         peaks=PEAKS, window=WINDOW, chips=4)


@pytest.mark.parametrize("name", ["idle_share", "decode_host_ms_p50",
                                  "prefill_stall_ms_p50"])
def test_shared_readers_read_as_on_the_chat_cell(name):
    # decode steps 0-10 and 20-30 ms with the chip busy 2-9 and 21-30 ms;
    # a prefill of 4 ms and one of 3 ms between them
    host = [("serving.decode", 0, 10 * MS), ("serving.prefill", 11 * MS,
                                              15 * MS),
            ("serving.prefill", 15 * MS, 18 * MS),
            ("serving.decode", 20 * MS, 30 * MS)]
    busy = [(2 * MS, 9 * MS), (21 * MS, 30 * MS)]
    want = {"idle_share": 100.0 * (1 - 0.016 / 0.1),
            "decode_host_ms_p50": 2.0, "prefill_stall_ms_p50": 7.0}[name]
    r = _host_reading(host, busy)
    assert reader(name)(r) == pytest.approx(want)
    chat = core.load_module(tiny.BENCH / "metrics" / f"{name}.chat.py").read
    assert chat(r) == pytest.approx(want)
    # the parent: no program spans, or nothing ran
    assert reader(name)(_host_reading([], [])) is None


def test_setup_compile_s(recorded):
    # compile seconds of the engine's spans that ended before the window
    recorded["records"] = [
        rec("serving.init", 10.0, 11.0, compile_s=2.0),
        rec("serving.prefill", 20.0, 30.0, compile_s=5.5),
        rec("train.step", 20.0, 30.0, compile_s=9.0),
        rec("serving.decode", 120.0, 121.0, compile_s=1.0)]
    assert reader("setup_compile_s")(reading()) == pytest.approx(7.5)
    recorded["dropped"] = 1
    assert reader("setup_compile_s")(reading()) is None
    recorded["dropped"], recorded["records"] = 0, []
    assert reader("setup_compile_s")(reading()) is None


def test_stream_holds_every_length_in_each_set():
    # any 64 consecutive requests of a set hold the same lengths, whatever
    # the seed; ids and order move with it
    drv = tiny.driver("serve_backlog")
    tr = tiny.traffic(CELL)
    a = drv.request_stream(tr, 2147483711, 50304)
    b = drv.request_stream(tr, 2147483659, 50304)
    assert len(a) == len(b) == tr["stream"]
    k = tr["stream_set"]
    for i in (0, k, 10 * k):
        lens = lambda s: sorted(len(r["prompt"]) for r in s[i:i + k])  # noqa: E731
        assert lens(a) == lens(b)
    assert [len(r["prompt"]) for r in a[:k]] != [len(r["prompt"])
                                                for r in b[:k]]
