"""The control, put in the program's place, must come out not correct.

The control is the plain reference computed with every matmul operand in
float8 e4m3, the precision below the configurations' bfloat16
(``bench/tools/control.py`` reads it on the chip at each cell's own size).
Here it is read at a size the CPU holds, against each cell's own limit:

* serving: at every position of a few sequences, the gap of the token the
  control puts first, below the float32 reference's best, in standard
  deviations of the reference's logits (the number the serving check
  compares; the control need not decode);
* training: the control follows the first steps from the seed's weights and
  batches, read by the training check's three gaps.
"""
import types

import numpy as np
import pytest

import tiny
from harness import core, serving
from test_faults import _train

CONTROL = core.load_module(tiny.BENCH / "tools" / "control.py")
REFERENCE = core.load_module(tiny.BENCH / "references" / "olmo_decoder.py")

# four layers at width 256: wide and deep enough for float8 rounding to
# move the top token by more than the limit, small enough for the CPU
SIZE = {"num_hidden_layers": 4, "hidden_size": 256, "vocab_size": 4096,
        "num_attention_heads": 2, "num_key_value_heads": 2}


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_serving_control_fails_the_limit(seed):
    cell = "olmo-1b.chat-poisson"
    cfg = tiny.config("olmo-1b", intermediate_size=512, **SIZE)
    params = serving.make_params(serving.program_config(cfg), seed)
    r = np.random.default_rng(seed)
    seqs = [types.SimpleNamespace(prompt=r.integers(1, 4096, 100).tolist(),
                                  tokens=r.integers(1, 4096, 200).tolist())
            for _ in range(2)]
    gaps = serving.served_gaps(REFERENCE, cfg, params, seqs, "fp8",
                               pick="control")
    limit = tiny.traffic(cell)["check"]["gap_limit"]
    assert gaps.size == 400
    assert gaps.max() > limit


def test_train_control_fails_a_limit():
    ctx = _train()
    row = CONTROL.train_seed(ctx, tiny.driver("train"))
    limits = ctx.traffic["check"]
    assert any(row["control"][k] > limits[k] for k in limits), row
