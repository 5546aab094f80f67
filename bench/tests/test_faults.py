"""Drive whole runs on the CPU (past the chip check) with the timed path
broken underneath, and see ``correct`` come out false; and see a sound
run come out true, and a run without a chip or without the program
print no result."""

import json
import os
import subprocess
import sys

import pytest

import repro.core.engine as eng
from conftest import BENCH, copy_layout

SECONDS = "1.5"


def _run(root, workload, capsys, trace=0, seed=11, extra=()):
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.run(["--workload", workload, "--seed", str(seed),
                  "--seconds", SECONDS, "--trace", str(trace), *extra],
                 root=root, require_tpu=False)
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return result


@pytest.mark.parametrize("workload", ["tiny_2d.refresh", "tiny_2d.query"])
def test_a_sound_run_is_correct(tiny_root, capsys, workload):
    res = _run(tiny_root, workload, capsys)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"]
    assert res["device"]["platform"] == "cpu"


def test_a_traced_run_reports_per_layer_metrics(tiny_root, capsys):
    dump = tiny_root / "programs.json"
    res = _run(tiny_root, "tiny_2d.query", capsys, trace=1,
               extra=("--dump-trace", str(dump)))
    assert res["correct"] is True
    # the CPU has no device trace: only the engine's counter is read
    assert set(res["metrics"]) == {"queries_per_eval_batch"}
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res
    record = json.loads(dump.read_text())
    assert record["device_kind"] == res["device"]["kind"]
    assert record["counters"]["eval.queries"] > 0
    assert record["programs"] == []          # no device planes off the chip


def _stale_ingest(monkeypatch):
    """A refresh that returns the tenant's state unchanged."""
    real = eng.CTEngine._dispatch_ingest

    def stale(self, tenant, grids):
        if tenant.surplus is not None:
            return tenant.surplus
        return real(self, tenant, grids)
    monkeypatch.setattr(eng.CTEngine, "_dispatch_ingest", stale)


def _altered_ingest(monkeypatch):
    """A refresh whose surplus is altered where it is produced."""
    real = eng.CTEngine._dispatch_ingest
    monkeypatch.setattr(eng.CTEngine, "_dispatch_ingest",
                        lambda self, t, g: real(self, t, g) * (1 + 1e-4))


def _half_batch(monkeypatch):
    """A batched eval that leaves out the first half of its rows."""
    real = eng._EVAL_BATCHED

    def half(surp, pts):
        out = real(surp, pts)
        return out.at[:(out.shape[0] + 1) // 2].set(0.0)
    half._cache_size = real._cache_size      # engine.stats() reads it
    monkeypatch.setattr(eng, "_EVAL_BATCHED", half)


def _altered_answer(monkeypatch):
    """Every answer altered where the eval produces it."""
    real = eng._EVAL_BATCHED

    def altered(surp, pts):
        return real(surp, pts) * (1 + 1e-4)
    altered._cache_size = real._cache_size
    monkeypatch.setattr(eng, "_EVAL_BATCHED", altered)


@pytest.mark.parametrize("workload,fault", [
    ("tiny_2d.refresh", _stale_ingest),
    ("tiny_2d.refresh", _altered_ingest),
    ("tiny_2d.query", _half_batch),
    ("tiny_2d.query", _altered_answer),
], ids=["refresh-state-unchanged", "refresh-surplus-altered",
        "query-half-batch-left-out", "query-answer-altered"])
def test_a_broken_timed_path_is_not_correct(tiny_root, capsys, monkeypatch,
                                            workload, fault):
    fault(monkeypatch)
    res = _run(tiny_root, workload, capsys)
    assert res["correct"] is False
    assert res["checks"]["answer_gap"]["value"] > \
        res["checks"]["answer_gap"]["limit"]


def _subprocess(cwd, code):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_without_a_chip_the_command_prints_no_result(tiny_root):
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny_2d.query",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny_root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_without_the_program_the_command_prints_no_result(tmp_path):
    root = copy_layout(tmp_path)
    os.unlink(root / "src")         # only BENCHMARK.json and bench/ remain
    from conftest import add_tiny_cells, allow_cpu
    add_tiny_cells(root)
    allow_cpu(root)
    code = ("import sys; sys.path.insert(0, 'bench'); import run; "
            "from pathlib import Path; "
            "sys.exit(run.run(['--workload', 'tiny_2d.query', '--seed', '1', "
            "'--seconds', '1'], root=Path('.'), require_tpu=False))")
    p = _subprocess(root, code)
    assert p.returncode != 0
    assert "repro" in p.stderr
    assert '"correct"' not in p.stdout
