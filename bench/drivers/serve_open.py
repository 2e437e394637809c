"""Open-loop serving: requests arrive on a Poisson schedule drawn from the
seed, whatever the server's state, and are timed from when they were due.

The loop is the body of ``PagedServingEngine.run``, repeated: submit every
request now due, admit, prefill each admission, one decode step.  It calls
only what ``run`` calls.  A prelude of the same schedule fills the slots
before the window opens (set-up, since the traffic needs it); requests due
inside the window are followed until they finish.

Time to first token ends when the host holds the token its prefill
sampled; each gap between tokens ends when ``decode_step`` returns.  Both
tails are over every request due in the window: one that never got its
first token counts the time until following ended, and the gaps of one
that never finished count as far as it was served.
"""
from __future__ import annotations

import json
import re
import time
import warnings

import numpy as np

from harness import core, serving, traffic as gen
from harness.serving import Timing

_TAIL = re.compile(r"^(ttft|itl)_p(\d+(?:\.\d+)?)_ms$")

# seconds past the close of the window that a due request may take to
# finish: the longest output (512 tokens) at ~0.1 s a decode step, after a
# wait for pages that reserved admission can impose
FOLLOW_S = 150.0


def setup(ctx):
    """Configuration, weights and engine."""
    cfg = serving.program_config(ctx.config)
    params = serving.make_params(cfg, ctx.args.seed)
    engine = serving.make_engine(cfg, params, ctx.traffic)
    return cfg, params, engine


def serve(ctx, engine, schedule: list[dict], seconds: float, record=None):
    """Run ``schedule`` against ``engine``.  The window opens at due time 0
    and closes ``seconds`` later; returns the timing of every request, the
    finished results by id, the ids due in the window, the queue length
    sampled after each step, and when following ended.

    ``record`` (a dict) gathers what the per-layer readers count inside the
    window: each prefill's real tokens and bucket, each decode step's
    attended depths."""
    from repro import sfu
    from repro.serving import GenRequest

    sched = engine.sched
    spans = ctx.spans
    sfu.reset_all_warnings()
    reqs = [GenRequest(f"r{i}", r["prompt"], max_new_tokens=r["max_new_tokens"])
            for i, r in enumerate(schedule)]
    prelude = -min(0.0, min(r["due"] for r in schedule))
    t_zero = time.perf_counter() + prelude
    times = {q.request_id: Timing(due=t_zero + r["due"])
             for q, r in zip(reqs, schedule)}
    in_window = {q.request_id for q, r in zip(reqs, schedule)
                 if 0.0 <= r["due"] < seconds}
    results: dict = {}
    queue_len: list[tuple[float, int]] = []
    nxt, opened, closed, t_close = 0, False, False, None
    ps = engine.page_size
    while True:
        now = time.perf_counter()
        if not opened and now >= t_zero:
            ctx.open_window()
            opened = True
        if opened and not closed and now >= t_zero + seconds:
            t_close = ctx.close_window()
            closed = True
        # every request of the schedule is submitted when due, the window's
        # last ones too when a step ran past the close
        while nxt < len(reqs) and times[reqs[nxt].request_id].due <= now:
            sched.submit(reqs[nxt])
            times[reqs[nxt].request_id].submitted = time.perf_counter()
            nxt += 1
        if closed and (in_window <= results.keys()
                       or now > t_close + FOLLOW_S):
            break
        counting = opened and not closed and record is not None
        for adm in sched.admit():
            rid = adm.request.request_id
            times[rid].admitted = time.perf_counter()
            with spans("prefill"):
                engine._prefill(adm)
            times[rid].tokens.append(time.perf_counter())
            if counting:
                n = len(adm.prefill_tokens)
                record.setdefault("prefill", []).append(
                    (n, max(ps, serving._pow2(n))))
        active = sched.active_slots()
        if active:
            ids = [sched.slot(i).request.request_id for i in active]
            depth = [int(engine.kv_len[i]) + 1 for i in active]
            with spans("decode"):
                engine.decode_step()
            t = time.perf_counter()
            for rid in ids:
                times[rid].tokens.append(t)
            if counting:
                record.setdefault("decode", []).append(depth)
        else:
            due = (times[reqs[nxt].request_id].due if nxt < len(reqs)
                   else now + 1e-3)
            time.sleep(min(max(due - time.perf_counter(), 0.0), 0.01))
        for r in sched.results()[len(results):]:
            results[r.request_id] = r
        if opened and not closed:
            queue_len.append((time.perf_counter() - t_zero, len(sched.queue)))
    # a request submitted but never admitted holds no slot; drop it
    return times, results, in_window, queue_len, time.perf_counter()


def tails(ctx, values: dict) -> dict:
    """The cell's end-to-end metrics named ``<ttft|itl>_p<q>_ms``: the q-th
    percentile of those values, in ms."""
    out = {}
    for m in core.metrics_for(ctx.bench, ctx.cell["name"], "end_to_end"):
        t = _TAIL.match(m["name"])
        if t:
            out[m["name"]] = serving.percentile(values[t[1]], float(t[2]))
    return out


def run(ctx) -> core.Outcome:
    tr = ctx.traffic
    cfg, params, engine = setup(ctx)
    schedule = gen.poisson_schedule(tr, ctx.args.seed, ctx.args.seconds,
                                    cfg.vocab_size)
    buckets, widths = serving.shapes(engine, schedule)
    core.log(f"{len(schedule)} requests, prefill buckets {buckets}, decode "
             f"widths {widths}")
    serving.warm_up(engine, buckets, widths)
    record: dict = {}
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        times, results, in_window, _, t_end = serve(
            ctx, engine, schedule, ctx.args.seconds, record)
    for msg in serving.bad_warnings(caught):
        core.log(f"warning: {msg}")

    window = [times[r] for r in sorted(in_window)]
    done = [rid for rid in in_window if rid in results
            and results[rid].finish_reason == "length"
            and len(results[rid].tokens) == len(times[rid].tokens)]
    failed = len(in_window) - len(done)
    ttft = [((t.tokens[0] if t.tokens else t_end) - t.due) * 1e3
            for t in window]
    gaps = [g * 1e3 for t in window for g in np.diff(t.tokens)]
    wait = [((t.admitted or t_end) - t.due) * 1e3 for t in window]
    late = [(t.submitted - t.due) * 1e3 for t in window if t.submitted]
    core.log(f"window: {len(in_window)} requests due, {len(done)} finished, "
             f"{len(gaps)} gaps; generator lateness p50 "
             f"{serving.percentile(late, 50):.3f} ms, max {max(late):.3f} ms")
    qs = (50, 75, 80, 90, 95, 99)
    core.log("ms " + json.dumps(
        {**{f"ttft_p{q}": serving.percentile(ttft, q) for q in qs},
         **{f"itl_p{q}": serving.percentile(gaps, q) for q in qs},
         "ttft_mean": float(np.mean(ttft)), "itl_mean": float(np.mean(gaps))}))
    e2e = tails(ctx, {"ttft": ttft, "itl": gaps})
    record["queue_wait_ms"] = wait
    record["lateness_ms"] = late
    ctx.memory_peak = core.memory_peak_bytes(ctx.devices)
    del engine

    checks = serving.check_served(ctx, params, {r: results[r] for r in done})
    return core.Outcome(attempted=len(in_window), failed=failed,
                        end_to_end=e2e, checks=checks, counts=record)
