"""The split between the generic harness and the architecture modules: the
configuration file picks its module by name, the generic files know no
model's shape, and a cell reaches the model through its module alone."""
import collections
import importlib.util
import os
import sys
import types

import pytest

from chipbench import archs, run, smoke, weights
from chipbench.archs import minicpm
from chipbench.testing import cpu_run  # noqa: F401  (fixture)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "minicpm-2b-train.vocab-half"

# What the generic harness calls on an architecture module (archs/__init__).
MODEL_CALLS = {"dims", "program_weights", "slices", "reference_view",
               "change_norms", "loss_fn", "train_step_flops",
               "program_fields", "configured", "smoke"}

# Per-leaf fingerprints of MiniCPM's weights at the smoke size, as the
# harness made them before its model code moved into archs/minicpm.py.
PINNED = {
    5: [3489270842, 0, 0, 65215057, 1210505459, 61029758, 0, 240944810,
        2811935301, 2501095243, 2902058118],
    2 ** 32 + 17: [762379101, 0, 0, 3099748668, 3626988423, 1578055433, 0,
                   3311650521, 4014601515, 375669491, 4281656952],
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_weights_keep_their_bits(seed):
    dm = minicpm.dims(smoke.config(CONFIG))
    got = weights.fingerprint(minicpm.program_weights(dm, seed))
    assert [int(x) for x in got] == PINNED[seed]


@pytest.mark.parametrize("path", [
    "kinds/train.py", "hooks.py", "run.py", "readings.py", "reference.py",
    "weights.py", "testing.py", "smoke.py", "faults.py"])
def test_generic_files_know_no_model_shape(path):
    with open(os.path.join(HERE, path)) as f:
        text = f.read()
    found = [w for w in ("scale_emb", "scale_depth", "dim_model_base",
                         "embed_scale", "logit_divisor", "archs.minicpm",
                         "archs import minicpm") if w in text]
    assert not found, found


@pytest.mark.parametrize("arch, says", [
    ("nosuch", "names arch 'nosuch', and there is no chipbench/archs/"
               "nosuch.py"),
    (None, "names no architecture module"),
])
def test_missing_arch_stops_the_run_before_the_chip(monkeypatch, capsys,
                                                    arch, says):
    load = run.load_cell

    def load_cell(name, root=run.ROOT):
        bench, cell, cfg, traffic = load(name, root)
        cfg = {k: v for k, v in cfg.items() if k != "arch"}
        return bench, cell, dict(cfg, arch=arch) if arch else cfg, traffic

    monkeypatch.setattr(run, "load_cell", load_cell)
    with pytest.raises(archs.UnknownArch, match=says):
        run.main(["--workload", "train.steady", "--seed", "3",
                  "--seconds", "1", "--trace", "0"])
    assert capsys.readouterr().out == ""


def _recording_arch(monkeypatch):
    """A module ``recording`` that counts each call and hands it to a fresh
    copy of ``archs/minicpm.py``, while the module the configuration file
    names refuses every call."""
    spec = importlib.util.spec_from_file_location(
        "chipbench.archs._minicpm_copy", minicpm.__file__)
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    calls = collections.Counter()
    rec = types.ModuleType("chipbench.archs.recording")
    for name in MODEL_CALLS:
        def call(*a, _fn=getattr(model, name), _name=name, **kw):
            calls[_name] += 1
            return _fn(*a, **kw)
        setattr(rec, name, call)

        def refuse(*a, _name=name, **kw):
            raise AssertionError(f"minicpm.{_name} called past the arch")
        monkeypatch.setattr(minicpm, name, refuse)
    monkeypatch.setitem(sys.modules, rec.__name__, rec)
    return calls


@pytest.mark.parametrize("cell", ["train.steady", "train.ckpt"])
def test_a_cell_reaches_the_model_through_its_arch_alone(cpu_run, monkeypatch,
                                                         cell):
    calls = _recording_arch(monkeypatch)
    load = run.load_cell

    def load_cell(name, root=run.ROOT):
        bench, c, cfg, traffic = load(name, root)
        return bench, c, dict(cfg, arch="recording"), traffic

    monkeypatch.setattr(run, "load_cell", load_cell)
    got = cpu_run(cell)
    assert got["result"]["correct"], got["checks"]
    assert set(calls) == MODEL_CALLS, calls
