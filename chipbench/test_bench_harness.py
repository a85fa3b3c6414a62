"""The harness: no TPU means no result; the benchmark file keeps to its
contract; a sound run at the smoke size comes out correct."""
import json
import math
import os
import re

import pytest

from chipbench import run
from chipbench.hooks import WHOLE_SAVES
from chipbench.testing import cpu_run  # noqa: F401  (fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_no_tpu_means_exit_2_and_no_result(capsys):
    rc = run.main(["--workload", "train.steady", "--seed", "3",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "no TPU" in out.err


def test_benchmark_file_keeps_to_its_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "traffic",
                                           w["traffic"] + ".json"))
        assert run.cell_metrics(bench, w, False) and \
            run.cell_metrics(bench, w, True)
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert set(m.get("workloads", names)) <= set(names)
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("cell", ["train.steady", "train.ckpt"])
def test_sound_run_is_correct(cpu_run, cell):
    got = cpu_run(cell)
    res = got["result"]
    assert res["correct"], got["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert got["info"]["compiles_in_window"] == 0
    for m in res["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    if cell == "train.ckpt":
        assert got["info"]["window_saves"] >= WHOLE_SAVES
        assert set(res["metrics"]) == {"train_tokens_per_s.ckpt", "resume_s",
                                       "setup_s"}
