"""A cell cut to a size the CPU runs in seconds, for the tests: the
configuration by its architecture's own ``smoke`` cut, the traffic mix to
2 x 32 tokens a step and, where it saves, a save every 6 steps."""
from __future__ import annotations

import copy
import json
import os

from chipbench import archs

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name: str) -> dict:
    """The configuration file ``configs/<name>.json``, cut by the
    architecture it names."""
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    return archs.load(cfg).smoke(cfg)


def traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        tr = json.load(f)
    tr = copy.deepcopy(tr)
    tr.update(batch=2, seq_len=32)
    if tr["ckpt_every"]:
        tr["ckpt_every"] = 6
    return tr
