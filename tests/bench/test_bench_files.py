"""BENCHMARK.json against the benchmark's contract, every file it names,
the refusal to run anywhere but on a TPU, and that a cell is added by
files and an entry alone, a new trunk family with it."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from tests.bench.conftest import TINY_CELLS

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    for word in cmd[1:]:
        if "/" in word or word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_configurations():
    from bench import harness

    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["why"])
        assert one_line(c["source"]) and c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        cfg = harness.load_config(c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(cfg["tiny_arch"]) <= set(cfg["arch"])


def test_workloads():
    from bench import traffic

    names = [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(names) // 2)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        traffic.load_mix(w["traffic"])


def test_metrics():
    from bench.metrics import reader

    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert "setup_s" in e2e and not set(e2e) & set(layer)
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert one_line(m["layer"]) and m["moves"] in e2e
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        reader(m["name"])
    for cell in cells:
        reports = lambda group: [m["name"] for m in group
                                 if cell in m.get("workloads", cells)]
        assert "setup_s" in reports(BENCH["end_to_end"])
        assert len(reports(BENCH["end_to_end"])) >= 2
        assert reports(BENCH["per_layer"])


def test_run_refuses_anything_but_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "TPU" in p.stderr
    assert p.stdout.strip() == ""


NEW_METRIC = '''"""Patches offered per second of the window."""


def read(run):
    return len(run.t_gen) / run.seconds
'''

#: a trunk family of its own name that computes as the ViT on the pixels
#: of the tokens that the invocation's placements cover (all that the
#: stitch put on the canvases, so only placements that reach it leave the
#: outputs unchanged); ``{plant}`` puts a fault into its forward
NEW_TRUNK = '''"""A trunk family that computes as the ViT on its live tokens."""
import numpy as np

from bench.trunks import vit

flops, params, bytes = vit.flops, vit.params, vit.bytes


def forward(params, canvases, records, arch, dtype="float32"):
    assert len(records) == len(canvases)
    p = arch["patch"]
    live = vit.live_mask(records, arch).repeat(p, 1).repeat(p, 2)
    raw = vit.forward(params, canvases * live[..., None], records, arch,
                      dtype)
    return raw{plant}
'''
#: the planted fault: the objectness logit of every cell raised by 1
PLANTED = " + np.array([1.0, 0, 0, 0, 0], np.float32)"

RUNNER = '''
import json, sys
sys.path[:0] = [".", {src!r}]
from bench import run
from tests.bench.conftest import tiny_config, tiny_mix
bench = run.load_benchmark()
cell = run.cell_of(bench, "tiny.new")
out = run.result_line(bench, cell, tiny_config(cell["config"]),
                      tiny_mix(cell["traffic"]), 2**31 + 9, 2.0, 0,
                      require_tpu=False, log=lambda m: None)
print(json.dumps(out))
'''


def run_new_cell(tmp_path, base, plant=""):
    """Copy the benchmark and its tests, add a configuration ``tiny`` on a
    new trunk module ``toy`` (with ``plant`` in its forward), a traffic
    mix, a metric and a cell, each a file or an entry of its own, and run
    the cell through the unchanged harness and ``conftest.py`` (here on
    the CPU, at the configuration's tiny size)."""
    for d in ("bench", "tests/bench"):
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench/trunks/toy.py").write_text(
        NEW_TRUNK.format(plant=plant))
    cfg = json.loads((ROOT / f"bench/configs/{base['config']}.json")
                     .read_text())
    cfg.update(name="tiny", trunk="toy")
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    mix = dict(json.loads((ROOT / f"bench/traffic/{base['traffic']}.json")
                          .read_text()), mix="tiny")
    (tmp_path / "bench/traffic/tiny4k.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/patches_offered.py").write_text(NEW_METRIC)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": cfg["source"],
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.new", "config": "tiny",
                               "traffic": "tiny4k", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "patches_offered",
                                "unit": "patches/s", "better": "higher",
                                "bound": 0.05, "source": "host_clock",
                                "workloads": ["tiny.new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", RUNNER.format(src=str(ROOT / "src"))],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=280)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("base", TINY_CELLS, ids=[c["config"]
                                                  for c in TINY_CELLS])
def test_a_cell_is_added_by_files_and_an_entry_alone(tmp_path, base):
    """A new configuration on a new trunk module, a traffic mix and a
    metric, each a file of its own, and one new entry in BENCHMARK.json:
    the unchanged harness finds them by name and runs the new cell, and
    its check is correct."""
    out = run_new_cell(tmp_path, base)
    assert out["correct"], out["checks"]
    assert out["metrics"]["patches_offered"]["value"] > 0
    assert {"setup_s", "billed_s_per_kpatch"} <= set(out["metrics"])


@pytest.mark.parametrize("base", TINY_CELLS, ids=[c["config"]
                                                  for c in TINY_CELLS])
def test_a_fault_in_the_named_trunk_module_is_not_correct(tmp_path, base):
    """The check compares with the trunk module the configuration names:
    a fault planted in that module's forward makes the run not correct,
    on the objectness it moved."""
    out = run_new_cell(tmp_path, base, plant=PLANTED)
    assert not out["correct"]
    checks = out["checks"]
    assert checks["obj_gap"]["value"] > checks["obj_gap"]["limit"], checks
