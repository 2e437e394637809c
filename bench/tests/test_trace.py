"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``): one prefill and four decode steps of olmo-1b cut to
two layers, each inside the driver's span (CPU)."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import trace  # noqa: E402

DATA = BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def red():
    meta = json.loads((DATA / "serve.json").read_text())
    r = trace.reduce_file(DATA / "serve.xplane.pb", set(meta["spans"]),
                          meta["window_s"])
    return r, meta


def test_op_names():
    assert trace.op_name("%_paged_decode.9 = (f32[2]) custom-call()") == \
        "_paged_decode"
    assert trace.op_name("%bitcast_dynamic-update-slice_fusion.5 = bf16[2]"
                         " fusion()") == "bitcast_dynamic-update-slice_fusion"
    assert trace.op_name("%while = (s32[]) while()") == "while"


def test_intervals():
    merged = trace.union([(0, 4), (2, 6), (8, 9)])
    assert merged == [(0, 6), (8, 9)]
    assert trace.total(merged) == 7
    assert trace.subtract(merged, [(1, 2), (5, 8)]) == [(0, 1), (2, 5),
                                                         (8, 9)]


def test_spans_found(red):
    r, meta = red
    names = [n for n, _, _ in r.spans]
    for name, count in meta["spans"].items():
        assert names.count(name) == count


def test_kernels_inside_their_spans(red):
    r, meta = red
    for k in meta["decode_kernels"]:
        assert r.op_seconds(k, "decode") > 0, k
    for k in meta["prefill_kernels"]:
        assert r.op_seconds(k, "prefill") > 0, k
    assert r.op_seconds("_paged_decode", "prefill") == 0


def test_device_time_lies_inside_the_spans(red):
    # device and host share one clock: the work of each step falls inside
    # the span that launched and waited for it
    r, _ = red
    inside = r.span_busy_s("prefill") + r.span_busy_s("decode")
    assert r.busy_s > 0
    assert inside >= 0.95 * r.busy_s


def test_busy_within_window_and_no_collectives(red):
    r, meta = red
    lo, hi = r._extent()
    assert r.busy_s <= (hi - lo) / 1e9
    assert r.exposed_collective_s(0) == 0


def test_breakdown(red):
    r, _ = red
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10
    assert all(isinstance(v, float) and v > 0 for _, v in b["device_ops"])
    assert any(k == "decode/_paged_decode" for k, _ in b["device_ops"])


def test_decode_step_roofline_under_100(red):
    # the recorded decode steps (2 of olmo-1b's layers, 4 slots after a
    # 200-token prompt) against their least time: a share, never above 100
    from harness import core
    from roofline import decode_step
    from roofline.common import least_seconds

    r, meta = red
    cfg = dict(json.loads((BENCH / "configs" / "olmo-1b.json").read_text()),
               num_hidden_layers=2)
    peaks = core.peaks_for(meta["device"]["kind"])
    steps = [[201 + k] * 4 for k in range(meta["spans"]["decode"])]
    least = sum(least_seconds(*decode_step.count(cfg, d), peaks)
                for d in steps)
    share = 100.0 * least / r.span_busy_s("decode")
    assert 0 < share < 100
