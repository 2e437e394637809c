"""A run with the timed path broken underneath must come out not correct.

Each test drives the rest of a run (the cell's driver, the program, the
reference and the comparison, with the cell's own limits) at a size the
CPU holds, skipping only the harness's look for a chip, with one fault
planted in the program's timed path:

* serving: a token altered where it is produced; a decode step that hands
  back its cache unchanged;
* training: a step that returns its state unchanged; half of the batch
  left out, the mean taken over the rest.

No cell runs across chips, so no exchange between chips can be left out.
"""
import tiny


def _chat(seed=3):
    cfg = tiny.config("olmo-1b")
    tr = tiny.traffic(
        "olmo-1b.chat-poisson", prompt={"median": 40, "min": 8, "max": 100},
        output={"median": 8, "min": 4, "max": 20}, rate_per_s=4.0,
        prelude_s=1.0, engine={"max_slots": 4, "page_size": 16,
                               "max_context": 128, "num_pages": 40},
        check={"served_tokens": 40, "min_tokens": 20})
    return tiny.context("olmo-1b.chat-poisson", cfg, tr, seed, 2.0)


def _train(seed=3):
    cfg = tiny.config("olmo-1b-pp2")
    tr = tiny.traffic("olmo-1b.train-2k", seq=64, batch=4)
    return tiny.context("olmo-1b.train-2k", cfg, tr, seed, 1.0)


def _exec_wrapped(monkeypatch, change):
    from repro.serving.engine import PagedServingEngine

    orig = PagedServingEngine._exec

    def broken(self, phase, args):
        logits, cache = orig(self, phase, args)
        return change(phase, logits, cache, args)

    monkeypatch.setattr(PagedServingEngine, "_exec", broken)


def test_serving_token_altered(monkeypatch):
    # every decode step's logits favour one fixed token
    _exec_wrapped(monkeypatch, lambda phase, lg, cache, args: (
        (lg.at[..., 7].add(1e3) if phase == "decode" else lg), cache))
    out = tiny.driver("serve_open").run(_chat())
    assert not out.correct


def test_serving_cache_unchanged(monkeypatch):
    # the decode step's appended K/V is dropped: the old cache comes back
    _exec_wrapped(monkeypatch, lambda phase, lg, cache, args: (
        lg, args[1] if phase == "decode" else cache))
    out = tiny.driver("serve_open").run(_chat())
    assert not out.correct


def _step_wrapped(monkeypatch, wrap):
    import repro.launch.steps as steps

    orig = steps.build_train_step

    def build(*a, **k):
        fn, *rest = orig(*a, **k)
        return (wrap(fn), *rest)

    monkeypatch.setattr(steps, "build_train_step", build)


def test_train_state_unchanged(monkeypatch):
    _step_wrapped(monkeypatch, lambda fn: (
        lambda state, batch: (state, fn(state, batch)[1])))
    out = tiny.driver("train").run(_train())
    assert not out.correct


def test_train_half_batch(monkeypatch):
    def half(fn):
        def step(state, batch):
            b = batch["tokens"].shape[0] // 2
            return fn(state, {k: v[:b] for k, v in batch.items()})
        return step

    _step_wrapped(monkeypatch, half)
    out = tiny.driver("train").run(_train())
    assert not out.correct


def test_unbroken_runs_are_correct():
    """The same small runs with nothing broken pass their limits."""
    assert tiny.driver("serve_open").run(_chat()).correct
    assert tiny.driver("train").run(_train()).correct
