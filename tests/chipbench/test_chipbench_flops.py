"""The yardstick's operation and byte counts against hand counts."""
import pytest

import tinycell  # noqa: F401  (puts the repository on the path)
from chipbench import flops

# H=4, 2 query heads and 1 key/value head of 2, 4 experts top-2 of width 3,
# vocabulary 10, 2 layers
TINY = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 2, "num_experts": 4, "num_experts_per_tok": 2,
        "moe_intermediate_size": 3, "vocab_size": 10, "num_hidden_layers": 2}


def test_active_params_by_hand():
    attn = 4 * 4 + 2 * 4 * 2 + 4 * 4        # wq, wk + wv, wo
    router = 4 * 4
    experts = 2 * 3 * 4 * 3                  # top-2 of gate, up, down
    assert flops.active_params(TINY) == 2 * (attn + router + experts) + 40


def test_train_flops_per_token_by_hand():
    # 6 N + 12 L (heads * head_dim) T, T = 5
    assert flops.train_flops_per_token(TINY, 5) == 6 * 312 + 12 * 2 * 4 * 5


def test_olmoe_at_published_widths():
    m = {"hidden_size": 2048, "num_attention_heads": 16,
         "num_key_value_heads": 16, "head_dim": 128, "num_experts": 64,
         "num_experts_per_tok": 8, "moe_intermediate_size": 1024,
         "vocab_size": 50304, "num_hidden_layers": 1}
    assert flops.active_params(m) == (4 * 2048 * 2048 + 2048 * 64
                                      + 8 * 3 * 2048 * 1024 + 2048 * 50304)
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(1.0719e9,
                                                                 rel=1e-4)


@pytest.mark.parametrize("etp", [1, 3])
def test_grouped_ffn_counts(etp):
    f = 3 // etp
    ops, byts = flops.grouped_ffn_fwd(TINY, rows=10, slot_weights=100,
                                      etp=etp)
    assert ops == 6 * 10 * 4 * f
    assert byts == 2 * (100 + 2 * 10 * 4)
    ops, byts = flops.grouped_ffn_bwd(TINY, rows=10, slot_weights=100,
                                      etp=etp)
    assert ops == 12 * 10 * 4 * f
    assert byts == 2 * (2 * 100 + 3 * 10 * 4)


def test_least_time_names_its_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(720, 36, peak) == (7.2, "compute")
    assert flops.least_time(720, 360, peak) == (36.0, "memory")
