"""bench/flops.py against counts done by hand."""
import json
from pathlib import Path

import pytest

from bench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_one_dense_layer_by_hand():
    # d=4, 2 heads of 2 (kv 1), ff 8, vocab 10, one layer, seq 3, no patches
    config = {"reference": "transformer",
              "published": {"hidden_size": 4, "num_attention_heads": 2,
                            "num_key_value_heads": 1, "intermediate_size": 8,
                            "vocab_size": 10, "num_hidden_layers": 1}}
    q, kv, o, mlp = 2 * 4 * 4, 2 * 2 * 4 * 2, 2 * 4 * 4, 3 * 2 * 4 * 8
    per_pos = q + kv + o + mlp                    # 32 + 32 + 32 + 192
    attention = 2 * 2 * 2 * 2 * (1 + 2 + 3)       # QK and PV over causal pairs
    head = 2 * 4 * 10 * 2                         # positions 0 and 1 have labels
    assert flops.per_sequence(config, 3) == per_pos * 3 + attention + head


def test_per_step_counts_forward_and_backward_over_every_peer():
    config = json.loads((CONFIGS / "qwen2-vl-2b.json").read_text())
    one = flops.per_sequence(config, 1024)
    w = {"mesh": "4", "batch": 4, "seq": 1024}
    assert flops.per_step(config, w) == pytest.approx(3 * 16 * one)
    assert flops.data_peers({"mesh": "2x2"}) == 2
    assert flops.data_peers({"mesh": "2x2x1"}) == 4


def test_published_models_per_token():
    qwen = json.loads((CONFIGS / "qwen2-vl-2b.json").read_text())
    mamba = json.loads((CONFIGS / "mamba2-2.7b.json").read_text())
    # 20 layers of 46.8 M matmul parameters, a 233 M-parameter tied head
    assert 3 * flops.per_sequence(qwen, 1024) / 1024 == pytest.approx(6.85e9, rel=0.02)
    # 16 layers of 40.3 M projection parameters plus the SSD, a 129 M head
    assert 3 * flops.per_sequence(mamba, 8192) / 8192 == pytest.approx(4.83e9, rel=0.02)
