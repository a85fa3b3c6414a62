"""The readers of the program's own spans, on a hand-made record: a traced
``train.ckpt`` run (two whole saves, the crashed save, two resumes) and a
traced ``train.steady`` run (two whole steps, one that raised in its
wait for the loss, the one that raised ``WindowClosed``)."""
import collections
import sys

import pytest

from chipbench import run
from repro import obs

MS = 1_000_000  # ns


def _ring(spans):
    """(id, parent, name, start ms, end ms, ok, attrs) rows as the ring."""
    return collections.deque(
        (i, p, name, a * MS, b * MS, ok, attrs)
        for i, p, name, a, b, ok, attrs in spans)


CKPT = [
    # a whole save: d2h 10 ms, packs 20 + 30 ms
    (1, None, "checkpoint", 0, 100, True, {"epoch": 100}),
    (2, 1, "d2h", 0, 10, True, {"epoch": 100, "bytes": 64}),
    (3, 1, "pack", 12, 32, True, {"epoch": 100, "host": "host0"}),
    (4, 1, "pack", 32, 62, True, {"epoch": 100, "host": "host1"}),
    # a whole save: d2h 14 ms, packs 25 + 35 ms
    (5, None, "checkpoint", 200, 300, True, {"epoch": 200}),
    (6, 5, "d2h", 200, 214, True, {"epoch": 200, "bytes": 64}),
    (7, 5, "pack", 215, 240, True, {"epoch": 200, "host": "host0"}),
    (8, 5, "pack", 240, 275, True, {"epoch": 200, "host": "host1"}),
    # the crashed save: everything inside it ended, the save did not
    (9, None, "checkpoint", 400, 900, False, {"epoch": 300}),
    (10, 9, "d2h", 400, 900, True, {"epoch": 300, "bytes": 64}),
    (11, 9, "pack", 900, 901, True, {"epoch": 300, "host": "host0"}),
    # two resumes, and one restore that raised
    (12, None, "restore", 1000, 1100, True, {"epoch": 200}),
    (13, 12, "load", 1000, 1060, True, {"epoch": 200, "bytes": 64}),
    (14, 12, "put", 1060, 1100, True, {"epoch": 200}),
    (15, None, "restore", 1200, 1300, True, {"epoch": 200}),
    (16, 15, "load", 1200, 1280, True, {"epoch": 200, "bytes": 64}),
    (17, 15, "put", 1280, 1300, True, {"epoch": 200}),
    (18, None, "restore", 1400, 9000, False, {"epoch": 200}),
    (19, 18, "load", 1400, 9000, True, {"epoch": 200, "bytes": 64}),
]

STEADY = [
    (1, None, "step", 0, 50, True, {"step": 4}),
    (2, 1, "data", 0, 1, True, {}),
    (3, 1, "h2d", 1, 2, True, {}),
    (4, 1, "train_step", 2, 4, True, {}),
    (5, 1, "loss_sync", 4, 50, True, {}),
    (6, None, "step", 50, 100, True, {"step": 5}),
    (7, 6, "data", 50, 51, True, {}),
    (8, 6, "h2d", 51, 52, True, {}),
    (9, 6, "train_step", 52, 56, True, {}),
    (10, 6, "loss_sync", 56, 100, True, {}),
    # a step that raised while it waited for its loss
    (11, None, "step", 100, 900, False, {"step": 6}),
    (12, 11, "loss_sync", 101, 102, False, {}),
    # the step whose prefetch raised WindowClosed
    (13, None, "step", 900, 1000, False, {"step": 7}),
    (14, 13, "data", 900, 1000, False, {}),
]


@pytest.mark.parametrize("name, spans, want", [
    ("ckpt_d2h_ms", CKPT, 12.0),
    ("ckpt_pack_ms", CKPT, 55.0),
    ("restore_load_ms", CKPT, 70.0),
    ("restore_put_ms", CKPT, 30.0),
    ("step_host_ms", STEADY, 5.0),
])
def test_reader_on_a_hand_made_record(monkeypatch, name, spans, want):
    monkeypatch.setattr(obs, "_RECORDS", _ring(spans))
    assert run._reader(name)({}) == pytest.approx(want)


@pytest.mark.parametrize("name", ["ckpt_d2h_ms", "ckpt_pack_ms",
                                  "restore_load_ms", "restore_put_ms",
                                  "step_host_ms"])
def test_reader_finds_nothing(monkeypatch, name):
    # An empty record, and a program with no ``repro.obs`` at all.
    monkeypatch.setattr(obs, "_RECORDS", collections.deque())
    assert run._reader(name)({}) is None
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert run._reader(name)({}) is None
