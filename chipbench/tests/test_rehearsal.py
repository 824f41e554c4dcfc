"""A whole run of the harness on the CPU at 0.25@96 (Pallas in interpret
mode), and the command's refusals.  Times from here are CPU times and say
nothing about the chip."""
import json
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from small import run_small

RUN = [sys.executable, str(BENCH / "run.py")]


def _keys(out):
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])


def test_backlog_run_end_to_end():
    out = run_small("reorder.backlog")
    _keys(out)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"setup_s", "throughput_rps"}
    assert all(c["value"] == 0 for c in out["checks"].values())


def test_open_loop_traced_run_on_a_cascade():
    # a budget that makes the scheduler stream the small model in tiles
    out = run_small("tile224k.poisson", trace=True, seconds=1.5,
                    arena_budget_bytes=40 * 1024)
    _keys(out)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert {"build_s", "compile_s", "queue_wait_ms.p50"} <= set(m)
    # the CPU has no device plane: no device metric may be reported
    assert "program_ms_per_dispatch.poisson" not in m
    assert "busy_s" in out["device"] and "window_s" in out["device"]


def _cmd(cwd, *extra):
    return subprocess.run(
        RUN[:1] + [str(cwd / "chipbench" / "run.py"), "--workload",
                   "reorder.backlog", "--seed", str(2**31 + 9),
                   "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})


def test_command_refuses_the_cpu():
    p = _cmd(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_command_refuses_without_program_sources(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cmd(tmp_path)
    assert p.returncode != 0 and not p.stdout.strip()


def test_command_refuses_an_unknown_model(tmp_path):
    """A configuration whose model has no file under ``models/``: exit 2
    at ``load_cell``, before JAX is asked for a chip."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    conf = tmp_path / "chipbench" / "configs" / \
        "mobilenet_v1_1.0_192_int8.reorder.json"
    config = json.loads(conf.read_text())
    config["model"] = "no_such_model"
    conf.write_text(json.dumps(config))
    p = _cmd(tmp_path)
    assert p.returncode == 2 and not p.stdout.strip()
    assert "cannot load workload" in p.stderr
    assert "no_such_model" in p.stderr


def test_unknown_device_kind_is_refused():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "cpu" not in peaks
    with pytest.raises(KeyError):
        peaks["cpu"]
