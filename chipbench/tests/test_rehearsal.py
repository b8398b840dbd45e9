"""CPU rehearsals of every cell at a tiny window, of a cell on four chips,
and the harness's refusals: no result off the chip, none without the
program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cell

ROOT = cell.ROOT
RUN = os.path.join(ROOT, "chipbench", "run.py")
CELLS = [w["name"] for w in cell.load_spec()["workloads"]]


def _run(args, cwd=ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(cwd, "chipbench",
                                                        "run.py"), *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_of_each_cell(name):
    p = _run(["--workload", name, "--seed", str(2**31 + 5), "--seconds",
              "0.3", "--trace", "0", "--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    assert last["metrics"] == {}          # a rehearsal writes no device metric
    assert last["device"]["platform"] == "cpu"
    assert last["rehearsal"] is True and list(last)[-1] == "check"
    assert "compiles_in_window:" in p.stdout
    assert "check: wrong_verdict 0 limit 0" in p.stderr


def test_interpret_mode_rehearsal():
    """The Pallas kernel itself, in interpret mode, on the k=1 cell."""
    p = _run(["--workload", "h32-k1-q4.saturate", "--seed", "11", "--seconds",
              "0.05", "--rehearse", "--interpret"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1])["correct"] is True


def test_no_chip_no_result():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert p.returncode == 2
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def _copy_benchmark(dst, spec=None):
    spec = spec or cell.load_spec()
    with open(dst / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    for d in spec["paths"]:
        shutil.copytree(os.path.join(ROOT, d), dst / d,
                        ignore=shutil.ignore_patterns(".trace", "__pycache__"))


def test_a_cell_on_four_chips(tmp_path):
    """An added cell whose 16 queues run on four chips: the rehearsal
    gives it four devices of the CPU and serves every packet right."""
    spec = cell.load_spec()
    cfg = dict(cell.config(spec, "h32-k16-q4"), queues=16)
    spec["configs"].append({"name": "h32-k16-q16x4", "source": "-",
                            "file": "chipbench/configs/h32-k16-q16x4.json",
                            "reduced": [], "why": "-"})
    spec["workloads"].append({"name": "h32-k16-q16x4.saturate",
                              "config": "h32-k16-q16x4",
                              "traffic": "saturate", "chips": 4, "why": "-"})
    _copy_benchmark(tmp_path, spec)
    with open(tmp_path / "chipbench/configs/h32-k16-q16x4.json", "w") as f:
        json.dump(cfg, f)
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    p = _run(["--workload", "h32-k16-q16x4.saturate", "--seed",
              str(2**31 + 7), "--seconds", "0.3", "--rehearse"],
             cwd=str(tmp_path))
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4 and last["device"]["visible"] == 4
    assert "check: wrong_verdict 0 limit 0" in p.stderr


def test_benchmark_alone_is_not_enough(tmp_path):
    _copy_benchmark(tmp_path)
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "0.1",
              "--rehearse"], cwd=str(tmp_path))
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
