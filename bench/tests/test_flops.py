"""Shape-derived FLOP and byte counts against hand counts at smollm-135m
widths (the dense family's counts and the shared ones), and the peaks
table."""
import json
from pathlib import Path

import pytest

from bench import flops as F
from bench.families import dense as D

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                  "smollm135m-granite8b-L16.json").read_text())
EDGE, CLOUD = CFG["edge"], CFG["cloud"]


def test_smollm_widths():
    assert (EDGE["d_model"], EDGE["num_heads"], EDGE["num_kv_heads"],
            EDGE["head_dim"], EDGE["d_ff"], EDGE["vocab_size"],
            EDGE["num_layers"]) == (576, 9, 3, 64, 1536, 49152, 30)


def test_layer_and_token_flops_by_hand():
    # q 576*576 + k, v 2*576*192 + o 576*576 + gate/up/down 3*576*1536
    per_layer = 331776 + 221184 + 331776 + 2654208
    assert D.layer_matmul_params(EDGE) == per_layer == 3538944
    # 30 layers + the tied head 49152*576, 2 FLOPs per multiply-add
    assert D.token_flops(EDGE) == 2 * (30 * 3538944 + 28311552) \
        == 268959744


def test_attention_counts_by_hand():
    assert F.prefill_ctx(4) == 10            # 1 + 2 + 3 + 4 keys
    assert F.decode_ctx(3, 2) == 9           # steps attend 4, then 5 keys
    # 4 * 9 heads * 64 * ctx * 30 layers
    assert D.attn_flops(EDGE, 10) == 4 * 9 * 64 * 10 * 30
    assert F.forward_flops(EDGE, 4, 10) == 4 * 268959744 + 4 * 9 * 64 * 300


def test_prefill_decode_verify_granite():
    # granite-8b layer: q, o 4096^2 each; k, v 4096*1024 each; mlp 3*4096*14336
    per = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert D.layer_matmul_params(CLOUD) == per
    assert D.token_flops(CLOUD) == 2 * (16 * per + 49152 * 4096)
    # a verify of 5 positions after 100 committed: contexts 101..105
    assert F.decode_ctx(100, 5) == 101 + 102 + 103 + 104 + 105


def test_paged_decode_at_live_lengths():
    w = F.paged_decode_attn(EDGE, [100, 200])
    assert w["flops"] == 4 * 9 * 64 * 300
    # K and V: 2 * 3 kv heads * 64 * 300 positions; q and o: 2 * 2 * 9 * 64
    assert w["bytes"] == 2 * (2 * 3 * 64 * 300 + 2 * 2 * 9 * 64) == 235008


def test_peaks_and_roofline():
    p = F.peaks("TPU v5 lite")
    assert p == {"flops": 197e12, "hbm_bytes_s": 819e9}
    with pytest.raises(KeyError):
        F.peaks("TPU v9 imaginary")
    t = F.roofline_time(197e12, 819e9 * 2, p)
    assert t == {"seconds": 2.0, "bound": "memory"}
