"""The benchmark's data-driven contract: every cell finds its files by
name, each configuration states where it comes from, new pieces are found
without editing the harness, and the harness refuses to run off the chip.
"""
import json
import math
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import cells, check  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def check_rules(bench, root):
    """The rules every BENCHMARK.json keeps, whatever cells and metrics it
    lists, with each cell resolved to its files under ``root``."""
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in bench["paths"]:
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (root / p).is_dir(), p
    script = bench["command"][1]
    assert any(script.startswith(p + "/") for p in bench["paths"])
    assert (root / script).is_file()
    secs = bench["run_seconds"]
    assert isinstance(secs, int) and 1 <= secs <= 51
    # a full check of 24 cells fits its 43,200 s
    assert (2 + 14 * 24) * (secs + 60) + 24 * 2 * 90 + 1200 <= 43200
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[kind]]
        assert len(names) == len(set(names)), kind
        for n in names:
            assert NAME.match(n), n
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells_ = {w["name"]: w for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert set(m.get("workloads", cells_)) <= set(cells_), m["name"]
    configs = {c["name"]: c for c in bench["configs"]}
    assert {w["config"] for w in cells_.values()} == set(configs)
    for c in configs.values():
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
    four = sum(w["chips"] == 4 for w in cells_.values())
    assert four <= max(1, len(cells_) // 2)
    for name, w in cells_.items():
        assert w["chips"] in (1, 4)
        cell = cells.resolve(name, bench, root / "benchmarks" / "chip")
        assert cell.config["name"] == w["config"]
        assert math.prod(cell.traffic["mesh"]["shape"]) == cell.chips
        assert set(cell.limits) >= set(check.NUMBERS)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer and set(cell.readers) == {
            m["name"] for m in cell.per_layer}
        assert callable(cell.reference.Reference)


def test_benchmark_json_keeps_the_rules():
    check_rules(BENCH, ROOT)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_by_name(workload):
    cell = cells.resolve(workload)
    w = next(x for x in BENCH["workloads"] if x["name"] == workload)
    assert cell.config["name"] == w["config"]
    assert cell.chips == w["chips"]
    for reader in cell.readers.values():
        assert callable(reader.read)


def test_a_cell_without_limits_does_not_resolve():
    """A cell whose limits were never set from its readings has no
    comparison, so it cannot run."""
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "smollm-360m.unset",
                               "config": "smollm-360m",
                               "traffic": "train-dp", "chips": 4,
                               "why": "test"})
    with pytest.raises(FileNotFoundError):
        cells.resolve("smollm-360m.unset", bench)


CONFIG_FILES = sorted((ROOT / "benchmarks" / "chip" / "configs").glob(
    "*.json"))


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_config_file_states_its_provenance(path):
    cfg = json.loads(path.read_text())
    assert cfg["name"] == path.stem
    entry = next((c for c in BENCH["configs"] if c["name"] == path.stem),
                 None)
    if entry is not None:
        assert ROOT / entry["file"] == path
        assert cfg["source"].startswith(entry["source"])
        assert set(cfg["reduced"]) == set(entry["reduced"])
    assert cfg["source"].startswith("https://huggingface.co/")
    for key, cut in cfg["reduced"].items():
        assert cfg[key] == cut["run"] != cut["published"]
    assert cfg["assumed"] and cfg["deployment"]
    assert (ROOT / "benchmarks" / "chip" / "reference"
            / f"{cfg['reference']}.py").is_file()


#: the entries of ``smollm-360m.dp4`` in BENCHMARK.json, by kind
DP4 = {"configs": {"smollm-360m"}, "workloads": {"smollm-360m.dp4"},
       "per_layer": {"coll_ms", "coll_exposed_ms"}}


def _add_missing(bench, kind, entries):
    """Append to ``bench[kind]`` the entries whose names it lacks."""
    there = {x["name"] for x in bench[kind]}
    bench[kind] += [e for e in entries if e["name"] not in there]


def test_dropped_in_files_are_found(tmp_path):
    """A configuration, a reference, a traffic mix, a metric and a cell's
    limits dropped into a copy are found by name, and a four-chip cell
    with its collective metrics is added by its limits and entries alone:
    no file that is there is edited, and the rules still hold. The copy
    leaves out ``smollm-360m.dp4``'s entries and limits, so that the
    four-chip cell is dropped in whether or not BENCHMARK.json has it."""
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "tests" / "bench").mkdir(parents=True)
    bench = json.loads(json.dumps(BENCH))
    for kind, names in DP4.items():
        bench[kind] = [x for x in bench[kind] if x["name"] not in names]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"]
                              if w not in DP4["workloads"]]
    (here / "limits" / "smollm-360m.dp4.json").unlink(missing_ok=True)
    before = {p.relative_to(here): p.read_bytes()
              for p in here.rglob("*") if p.is_file()}
    (here / "reference" / "plain_lm.py").write_bytes(
        (here / "reference" / "dense_decoder.py").read_bytes())
    cfg = json.loads((here / "configs" / "smollm-360m.json").read_text())
    cfg.update(name="tiny-lm", num_hidden_layers=2, reference="plain_lm")
    (here / "configs" / "tiny-lm.json").write_text(json.dumps(cfg))
    mix = json.loads((here / "traffic" / "train-single.json").read_text())
    mix["seq_len"] = 64
    (here / "traffic" / "short-mix.json").write_text(json.dumps(mix))
    (here / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.steps) if ctx.steps else None\n")
    limits = (here / "limits" / "qwen1.5-4b.train.json").read_text()
    for cell in ("tiny-lm.short", "smollm-360m.dp4"):
        (here / "limits" / f"{cell}.json").write_text(limits)
    _add_missing(bench, "configs", [
        {"name": name, "source": "https://huggingface.co/x",
         "file": f"benchmarks/chip/configs/{name}.json", "reduced": [],
         "why": "test"} for name in ("tiny-lm", "smollm-360m")])
    _add_missing(bench, "workloads", [
        {"name": "tiny-lm.short", "config": "tiny-lm",
         "traffic": "short-mix", "chips": 1, "why": "test"},
        {"name": "smollm-360m.dp4", "config": "smollm-360m",
         "traffic": "train-dp", "chips": 4, "why": "test"}])
    _add_missing(bench, "per_layer", [
        {"name": "steps_seen", "unit": "steps", "better": "higher",
         "source": "device_trace", "layer": "train step",
         "moves": "step_ms"}] + [
        {"name": name, "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "collectives",
         "moves": "step_ms", "workloads": ["smollm-360m.dp4"]}
        for name in ("coll_ms", "coll_exposed_ms")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    check_rules(bench, tmp_path)
    cell = cells.resolve("tiny-lm.short", here=here)
    assert cell.config["num_hidden_layers"] == 2
    assert cell.traffic["seq_len"] == 64
    assert cell.reference.__file__.endswith("plain_lm.py")
    assert "steps_seen" in cell.readers and "coll_ms" not in cell.readers
    ctx = type("Ctx", (), {"steps": 7})()
    assert cell.readers["steps_seen"].read(ctx) == 7.0
    dp4 = cells.resolve("smollm-360m.dp4", here=here)
    assert dp4.chips == 4
    assert {"coll_ms", "coll_exposed_ms", "steps_seen"} <= set(dp4.readers)
    # the metric with no "workloads" key reaches the cells already there
    assert "steps_seen" in cells.resolve("qwen1.5-4b.train",
                                         here=here).readers
    assert before == {p: (here / p).read_bytes() for p in before}


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen1.5-4b.train", "--seed", "2147483700", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_harness_exits_nonzero_on_cpu():
    proc = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_harness_exits_nonzero_without_the_program(tmp_path):
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _no_result(proc)
