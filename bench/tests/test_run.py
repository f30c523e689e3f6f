"""The command and ``BENCHMARK.json``: the contract's shape, the refusal
of a machine without the chip, a cell added as data only, and a
configuration that brings a reference module of its own."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench.lib import program
from bench.tests import smoke

SPEC = smoke.load("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_spec_has_the_contract_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"] and SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert os.path.isfile(os.path.join(smoke.ROOT, c["file"])) and c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(SPEC["workloads"]) // 2)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    names = [m["name"] for m in SPEC["end_to_end"]]
    for m in SPEC["per_layer"]:
        assert m["moves"] in names and m["layer"] and "\n" not in m["layer"]
    all_names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in SPEC[k]]
    assert len(all_names) == len(set(all_names))


def test_every_cell_finds_its_files_by_name():
    cells = {w["name"] for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        mix = smoke.load("bench", "traffic", f"{w['traffic']}.json")
        assert os.path.isfile(os.path.join(smoke.ROOT, "bench", "loops", f"{mix['loop']}.py"))
        assert os.path.isfile(os.path.join(smoke.ROOT, "bench", "limits", f"{w['name']}.json"))
        reports = [m for m in SPEC["end_to_end"] if w["name"] in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in reports] and len(reports) >= 2
        assert any(w["name"] in m.get("workloads", cells) for m in SPEC["per_layer"])
    for m in SPEC["per_layer"]:
        assert os.path.isfile(os.path.join(smoke.ROOT, "bench", "metrics", f"{m['name']}.py"))


def test_every_configuration_is_used():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


def test_the_command_refuses_a_machine_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SPEC["workloads"][0]["name"],
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=smoke.ROOT, capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""


def add_cell(root, cfg: dict) -> str:
    """Copy the benchmark into ``root`` with one more configuration
    (``cfg``, at smoke size), a smoke-size chat mix and a cell of the
    two, all data; returns the cell's name."""
    shutil.copytree(os.path.join(smoke.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    (root / "bench" / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny-chat.json").write_text(json.dumps(smoke.mix("prefix-chat")))
    cell = f"serve.{cfg['name']}.tiny-chat"
    (root / "bench" / "limits" / f"{cell}.json").write_text(
        (root / "bench" / "limits" / "serve.h2o-danube-1.8b.prefix-chat.json").read_text())
    spec["configs"].append({"name": cfg["name"], "source": cfg["source"],
                            "file": f"bench/configs/{cfg['name']}.json",
                            "reduced": cfg["reduced"], "why": "test"})
    spec["workloads"].append({"name": cell, "config": cfg["name"], "traffic": "tiny-chat",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "serve.h2o-danube-1.8b.prefix-chat" in m.get("workloads", []):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


def run_added(root, cell: str) -> dict:
    code = (
        "import sys, json; sys.path[:0] = [{root!r}, {src!r}]\n"
        "from bench import run\n"
        "print(json.dumps(run.run_cell({cell!r}, 99, 1.0, False, check_chip=False)))\n"
    ).format(root=str(root), src=os.path.join(smoke.ROOT, "src"), cell=cell)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_cell_added_as_data_only_runs(tmp_path):
    """A new configuration, mix, limit and cell, all data, run by the
    harness as it stands."""
    root = tmp_path / "checkout"
    cfg = dict(smoke.config("h2o-danube-1.8b"), name="h2o-danube-1.8b-tiny")
    out = run_added(root, add_cell(root, cfg))
    assert out["correct"] and set(out["metrics"]) == {"setup_s", "ttft_p90_ms", "itl_p99_ms"}


PROBE = """
from bench.reference.dense_gqa import *  # noqa: F401,F403
from bench.reference import dense_gqa


def _record(name):
    with open({record!r}, "a") as f:
        f.write(name + "\\n")


def logits_at(*args, **kwargs):
    _record("logits_at")
    return dense_gqa.logits_at(*args, **kwargs)


def decode_step_bytes(*args, **kwargs):
    _record("decode_step_bytes")
    return dense_gqa.decode_step_bytes(*args, **kwargs)
"""


def test_a_configuration_naming_another_reference_module_runs(tmp_path):
    """A configuration whose ``reference`` is a module of its own, added
    as new files, is run by the harness as it stands, and the harness
    takes its correctness and its counts from that module."""
    root = tmp_path / "checkout"
    record = tmp_path / "probe_calls.txt"
    cfg = dict(smoke.config("h2o-danube-1.8b"), name="h2o-danube-1.8b-probe",
               reference="bench/reference/probe_ref.py")
    cell = add_cell(root, cfg)
    (root / "bench" / "reference" / "probe_ref.py").write_text(PROBE.format(record=str(record)))
    out = run_added(root, cell)
    assert out["correct"], out["checks"]
    assert set(record.read_text().split()) == {"logits_at", "decode_step_bytes"}


@pytest.mark.parametrize("path", [
    "bench/lib/flops.py", "bench/reference/../lib/flops.py", "/bench/reference/dense_gqa.py",
    "bench/reference/__init__.py", "bench/reference/absent.py", None,
])
def test_a_reference_outside_bench_reference_is_refused(path):
    config = {"name": "some-model", "reference": path}
    with pytest.raises(ValueError, match="some-model"):
        program.reference(config)
