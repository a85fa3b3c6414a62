"""Operations a training step requires, counted from the configuration.

Model FLOPs in the usual sense: 6 per matmul parameter per token (2 forward,
4 backward), plus 12 * layers * seq_len * (heads * head_dim) per token for
the attention scores and their weighting, forward and backward.  The tied
head counts once, over the configured vocabulary (rows the program pads its
table with are not work the model requires).  Norms, rotary embedding, softmax and the
optimizer are left out.  Recomputation is not counted.
"""
from __future__ import annotations


def matmul_params(dm: dict) -> int:
    d, H, KV, hd, F = dm["d"], dm["heads"], dm["kv_heads"], dm["hd"], dm["ffn"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 3 * d * F
    return dm["vocab"] * d + dm["layers"] * (attn + mlp)


def train_step_flops(dm: dict, batch: int, seq_len: int) -> int:
    tokens = batch * seq_len
    attention = 12 * dm["layers"] * seq_len * dm["heads"] * dm["hd"]
    return tokens * (6 * matmul_params(dm) + attention)
