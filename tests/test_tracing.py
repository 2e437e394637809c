"""repro.tracing: spans, counters and compile attribution, and the spans the
paged engine and the train launcher record (CPU)."""
import dataclasses
import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_reduced_config
from repro.models import Model
from repro.serving import GenRequest, PagedServingEngine

DECODE_CHILDREN = ["serving.decode.grow", "serving.decode.inputs",
                   "serving.decode.run", "serving.decode.sample",
                   "serving.decode.commit"]
# the names the benchmark's own spans take; a program span of one of these
# names would be mistaken for the benchmark's
DRIVER_SPANS = {"prefill", "decode", "train_step"}


def test_nesting_and_parents_across_threads():
    rec = tracing.Recorder()
    ready = threading.Barrier(2)

    def work(tag):
        with rec.span(f"{tag}.outer", who=tag) as outer:
            ready.wait(timeout=10)  # both outers open at once
            with rec.span(f"{tag}.inner"):
                ready.wait(timeout=10)
            outer.attrs["done"] = 1

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    by_name = {r.name: r for r in rec.records()}
    assert set(by_name) == {"a.outer", "a.inner", "b.outer", "b.inner"}
    for tag in "ab":
        outer, inner = by_name[f"{tag}.outer"], by_name[f"{tag}.inner"]
        assert outer.parent is None
        assert inner.parent == outer.id  # its own thread's span
        assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
        assert outer.attrs == {"who": tag, "done": 1}


def test_records_filtered_by_time():
    rec = tracing.Recorder()
    spans = []
    for i in range(3):
        with rec.span("s", i=i) as sp:
            pass
        spans.append(sp)
    got = rec.records(since=spans[1].t0)
    assert [r.attrs["i"] for r in got] == [1, 2]
    got = rec.records(until=spans[1].t1)
    assert [r.attrs["i"] for r in got] == [0, 1]
    assert rec.records(since=spans[1].t0, until=spans[1].t1)[0].id == \
        spans[1].id


def test_bounded_buffer_counts_dropped():
    rec = tracing.Recorder(capacity=4)
    spans = []
    for i in range(6):
        with rec.span("s", i=i) as sp:
            pass
        spans.append(sp)
    everything = rec.records()
    assert [r.attrs["i"] for r in everything] == [2, 3, 4, 5]
    assert everything.dropped == 2
    # a range that starts after the oldest kept span ended lost nothing; one
    # that starts before may have
    assert rec.records(since=spans[3].t0).dropped == 0
    assert rec.records(since=spans[2].t0).dropped == 2


def test_compile_goes_to_the_innermost_span():
    rec = tracing.Recorder()
    x = jnp.arange(7.0)

    @jax.jit
    def inner(v):
        return jnp.sin(v) * 3.0

    @jax.jit
    def outer(v):  # traces ``inner`` inside its own trace
        return inner(v) + jnp.cos(v) * 0.5

    with rec.span("parent"):
        with rec.span("child") as child:
            jax.block_until_ready(outer(x))
        with rec.span("again") as again:
            jax.block_until_ready(outer(x))  # cached: nothing compiles
    by_name = {r.name: r for r in rec.records()}
    assert by_name["child"].attrs["compile_s"] > 0
    # nested compile events count once: never more than the span lasted
    assert by_name["child"].attrs["compile_s"] <= child.t1 - child.t0
    assert by_name["parent"].attrs.get("compile_s", 0.0) == 0.0
    assert again.attrs.get("compile_s", 0.0) == 0.0


def test_many_nested_compiles_count_once():
    # a program that traces a hundred jitted functions inside its own trace
    # (as a model step traces each layer's kernels), each taking 10 ms to
    # trace: counting them again inside the outer trace would pass the span
    rec = tracing.Recorder()

    def layer(v, k):
        time.sleep(0.01)  # runs while tracing only
        return jnp.tanh(v * (k + 1.0))

    layers = [jax.jit(lambda v, k=k: layer(v, k)) for k in range(100)]

    @jax.jit
    def step(v):
        for f in layers:
            v = f(v)
        return v

    with rec.span("step") as sp:
        jax.block_until_ready(step(jnp.arange(5.0)))
    assert 0 < sp.attrs["compile_s"] <= sp.t1 - sp.t0


# ---------------------------------------------------------------------------
# the paged engine's spans


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """A paged session of repro-100m (reduced) under the profiler; each
    decode step's kv_len sum is noted before the step runs."""
    cfg = dataclasses.replace(get_reduced_config("repro-100m"),
                              act_impl="fused")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    reqs = [GenRequest(f"r{i}", rng.integers(1, 500, size=n).tolist(), m)
            for i, (n, m) in enumerate([(11, 4), (27, 9), (5, 6)])]
    start = time.perf_counter()
    engine = PagedServingEngine(model, params, max_slots=2, page_size=16,
                                max_context=64)
    held = []
    step = engine.decode_step

    def noted_step():
        held.append(int(engine.kv_len.sum()))
        return step()

    engine.decode_step = noted_step
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        results = engine.run(reqs)
    assert sorted(len(r.tokens) for r in results) == [4, 6, 9]
    recs = [r for r in tracing.records(since=start)
            if r.name.startswith("serving.")]
    return engine, recs, held, trace_dir


def _children(recs, parent):
    return sorted((r for r in recs if r.parent == parent.id),
                  key=lambda r: r.t0)


def test_decode_step_span_tree(session):
    _, recs, held, _ = session
    steps = [r for r in recs if r.name == "serving.decode"]
    assert len(steps) == len(held) > 0
    for step in steps:
        assert step.parent is None
        kids = _children(recs, step)
        assert [k.name for k in kids] == DECODE_CHILDREN
        assert all(step.t0 <= k.t0 <= k.t1 <= step.t1 for k in kids)
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))


def test_prefill_span_tree_and_counters(session):
    _, recs, _, _ = session
    pre = sorted((r for r in recs if r.name == "serving.prefill"),
                 key=lambda r: r.t0)
    assert [p.attrs["request_id"] for p in pre] == ["r0", "r1", "r2"]
    assert [(p.attrs["tokens"], p.attrs["bucket"]) for p in pre] == [
        (11, 16), (27, 32), (5, 16)]
    for p in pre:
        assert [k.name for k in _children(recs, p)] == [
            "serving.prefill.inputs", "serving.prefill.run",
            "serving.prefill.sample"]
    admitted = sum(r.attrs["admitted"] for r in recs
                   if r.name == "serving.admit")
    assert admitted == 3


def test_decode_counters(session):
    engine, recs, held, _ = session
    steps = [r.attrs for r in recs if r.name == "serving.decode"]
    assert [s["tokens_held"] for s in steps] == held
    for s in steps:
        assert 0 < s["tokens_held"] <= s["token_capacity"]
        assert s["token_capacity"] % engine.page_size == 0
        assert 1 <= s["active"] <= engine.max_slots
        assert s["n_cols"] in (1, 2, 4)


def test_compile_lands_in_run_spans(session):
    _, recs, _, _ = session
    runs = {r.name: r.attrs.get("compile_s", 0.0) for r in recs
            if r.name.endswith(".run") and r.attrs.get("compile_s")}
    assert set(runs) == {"serving.prefill.run", "serving.decode.run"}


def test_names_in_the_profiler_trace(session):
    _, recs, _, trace_dir = session
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    names.update(ev.name for ev in line.events)
    assert {"PjitFunction(serving_prefill)",
            "PjitFunction(serving_decode)"} <= names
    assert "PjitFunction(call)" not in names
    assert set(DECODE_CHILDREN) | {"serving.decode", "serving.prefill",
                                   "serving.admit"} <= names
    assert not names & DRIVER_SPANS
    assert not {r.name for r in recs} & DRIVER_SPANS


def test_train_step_spans():
    from repro.launch.train import run

    start = time.perf_counter()
    out = run(["--arch", "repro-100m", "--reduced", "--batch", "2", "--seq",
               "64", "--steps", "3", "--log-every", "100"])
    steps = [r for r in tracing.records(since=start) if r.name == "train.step"]
    assert len(steps) == 3
    assert out["step_seconds"] == [r.t1 - r.t0 for r in steps]
    assert steps[0].attrs["compile_s"] > 0
    assert not {r.name for r in tracing.records(since=start)} & DRIVER_SPANS
