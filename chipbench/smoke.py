"""A cell cut to a size the CPU runs in seconds, for the tests.

The program's own reduced MiniCPM (``RunConfig(use_smoke=True)``): widths
64/4/2/16/128, vocabulary 512, 2 layers, with the published scalars
(``scale_emb``, the residual scale of 40 layers, logits divided by 9).
"""
from __future__ import annotations

import copy
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs",
                           "minicpm-2b-train.vocab-half.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               vocab_size=512, dim_model_base=64 / 9)
    cfg["as_run"]["padded_vocab_rows"] = 512
    cfg["program"] = {"arch": "minicpm-2b", "use_smoke": True,
                      "n_layers": None}
    return cfg


def traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        tr = json.load(f)
    tr = copy.deepcopy(tr)
    tr.update(batch=2, seq_len=32)
    if tr["ckpt_every"]:
        tr["ckpt_every"] = 6
    return tr
