"""Each count against a number worked by hand for one shape (CPU)."""
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from roofline import attn_bwd, decode_step, glu_bwd, model_flops, paged_decode  # noqa: E402
from roofline.common import least_seconds  # noqa: E402

OLMO = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())
PEAKS = json.loads((BENCH / "roofline" / "peaks.json").read_text())["devices"]


def test_paged_decode_two_requests():
    # 4 * 16 heads * 128 * (100 + 300) positions; K and V of 400 positions
    # at 16 heads x 128 x 2 bytes, plus q and out of 2 rows
    assert paged_decode.count([100, 300], heads=16, kv_heads=16,
                              head_dim=128) == (3_276_800, 3_293_184)


def test_glu_backward_train_rows():
    # 4 * 8192 * 2048 * 8192; (8192*2048 + 2*2048*8192 + 3*8192*8192) * 2
    assert glu_bwd.count(8192, d_model=2048, d_ff=8192) == (
        549_755_813_888, 503_316_480)


def test_attention_backward_causal():
    # 2048*2049/2 = 2,098,176 pairs; 8 * 128 * pairs * 16 heads * 4 rows;
    # 8 tensors of 4*2048*16*128 bf16 + f32 stats 4*16*2048
    assert attn_bwd.count(4, 2048, heads=16, kv_heads=16, head_dim=128) == (
        137_506_062_336, 268_959_744)


def test_olmo_1b_model_flops():
    # per layer 2048*48*128 + 16*128*2048 + 3*2048*8192 = 67,108,864;
    # 16 layers; head 2048 * 50304
    assert model_flops.body_params(OLMO) == 1_073_741_824
    assert model_flops.head_params(OLMO) == 103_022_592
    # one token at depth 100: 2 * (body + head) + 4*16*128*100*16
    assert model_flops.decode(OLMO, [100]) == 2_366_636_032
    # 8 tokens prefilled: 2*body*8 + 2*head + 4*2048*36*16
    assert model_flops.prefill(OLMO, 8) == (
        17_179_869_184 + 206_045_184 + 4_718_592)


def test_olmo_1b_decode_step():
    # two requests at depths 100 and 300: 2 * (body + head) per token,
    # 2 * 1,176,764,416 * 2, plus 4*16*128*400*16 for attention; every
    # weight once at bf16, 1,176,764,416 * 2; K and V of 400 positions in
    # 16 layers at 16 heads x 128 x 2 bytes, 2*16*16*128*2*400
    flops, nbytes = decode_step.count(OLMO, [100, 300])
    assert flops == 4_707_057_664 + 52_428_800
    assert nbytes == 2_353_528_832 + 52_428_800


def test_least_time_takes_the_larger_bound():
    v5e = PEAKS["TPU v5 lite"]
    assert least_seconds(197e12, 0, v5e) == 1.0
    assert least_seconds(0, 819e9, v5e) == 1.0
    assert least_seconds(197e12, 2 * 819e9, v5e) == 2.0
