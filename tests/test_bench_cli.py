"""Benchmark-CLI liveness: the report/bench/gate entry points must keep
running end-to-end.  Each shells out in --smoke mode (tiny shapes, seconds)
so argument parsing, imports, and output paths can never silently bit-rot."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable, "-m", *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.slow
def test_telemetry_report_smoke_cli():
    r = _run("benchmarks.telemetry_report", "--smoke")
    assert r.returncode == 0, r.stderr
    assert "smoke_3x3" in r.stdout
    assert "modes:" in r.stdout


@pytest.mark.slow
def test_benchmarks_run_smoke_cli_and_regression_gate(tmp_path):
    bench = str(tmp_path / "bench.json")
    r = _run("benchmarks.run", "--smoke", "--bench-json", bench)
    assert r.returncode == 0, r.stderr
    assert "Paper-fidelity gate" in r.stdout
    assert "FAIL" not in r.stdout
    with open(bench) as f:
        rec = json.load(f)
    assert rec["smoke"]
    assert list(rec["networks"]) == ["smoke", "smoke_fused"]
    assert len(rec["networks"]["smoke"]["layers"]) == 4
    # the fused run records the per-block HBM delta, and every block saves
    fd = rec["fused_delta"]["smoke"]
    assert len(fd["blocks"]) == 4
    assert all(b["fused_bytes_mb"] < b["unfused_bytes_mb"]
               for b in fd["blocks"])
    assert "fused epilogue [smoke]" in r.stdout

    # the gate passes against the record itself...
    r = _run("benchmarks.check_regression", "--baseline", bench,
             "--candidate", bench)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASS" in r.stdout
    # ...and exits nonzero on an injected slowdown
    r = _run("benchmarks.check_regression", "--baseline", bench,
             "--candidate", bench, "--inject-slowdown", "10")
    assert r.returncode != 0
    assert "PERF REGRESSION" in r.stdout


@pytest.mark.slow
def test_benchmarks_run_sparse_smoke_cli_and_sparse_gate(tmp_path):
    """--sparse rides the smoke bench: the record gains the sparse twin net
    and the per-layer dense-vs-sparse delta, the gate holds the sparse
    invariant on it, and the injection self-test proves the invariant trips."""
    bench = str(tmp_path / "bench.json")
    r = _run("benchmarks.run", "--smoke", "--sparse", "--bench-json", bench)
    assert r.returncode == 0, r.stderr
    with open(bench) as f:
        rec = json.load(f)
    assert list(rec["networks"]) == ["smoke", "smoke_fused", "smoke_sparse"]
    sd = rec["sparse_delta"]["smoke"]
    pruned = [e for e in sd["layers"] if e["pruned"]]
    assert len(pruned) == 4 and sd["pruned_layers"] == 4
    # the measured invariant: strictly fewer bytes per pruned layer
    assert all(e["sparse_bytes_mb"] < e["dense_bytes_mb"] for e in pruned)
    assert all(0.0 < e["keep_fraction"] < 1.0 for e in pruned)
    assert "sparse delta [smoke]" in r.stdout

    # the gate passes the record against itself...
    r = _run("benchmarks.check_regression", "--baseline", bench,
             "--candidate", bench)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "smoke sparse: 4 pruned layers" in r.stdout
    # ...and the sparse-invariant injection must trip it
    r = _run("benchmarks.check_regression", "--baseline", bench,
             "--candidate", bench, "--inject-sparse-violation")
    assert r.returncode != 0
    assert "not strictly below its dense twin" in r.stdout
    # a uniform slowdown scales both sides of the sparse delta, so it trips
    # the perf bands without faking a sparse-invariant violation
    r = _run("benchmarks.check_regression", "--baseline", bench,
             "--candidate", bench, "--inject-slowdown", "10")
    assert r.returncode != 0
    assert "not strictly below" not in r.stdout


@pytest.mark.slow
def test_regression_gate_smoke_against_committed_baseline():
    """Tier-1 perf gate: fresh smoke measurement vs the committed BENCH_10
    baseline — catches fused-path and sparse-path regressions at merge time."""
    assert os.path.exists(os.path.join(REPO, "BENCH_10.json")), \
        "BENCH_10.json baseline missing (benchmarks.run --bench-json " \
        "--tuned --sparse)"
    r = _run("benchmarks.check_regression", "--smoke")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "perf gate: PASS" in r.stdout
    # the smoke filter really selected the smoke nets, fused and sparse
    assert "smoke_fused:" in r.stdout
    assert "smoke_sparse:" in r.stdout
    assert "smoke sparse:" in r.stdout
    # the baseline is tuned, so the fresh run re-measures the tuned deltas
    assert "smoke tuning:" in r.stdout


@pytest.mark.slow
def test_autotune_smoke_cli(tmp_path):
    """Tier-1 liveness for the tuner: search the smoke keys, write a table."""
    out = str(tmp_path / "table.json")
    r = _run("benchmarks.autotune", "--smoke", "--out", out)
    assert r.returncode == 0, r.stderr
    assert "unique shape keys tuned" in r.stdout
    with open(out) as f:
        doc = json.load(f)
    assert doc["entries"], "tuner wrote an empty table"
    assert all(k.startswith(("conv2d|", "gemm|")) for k in doc["entries"])
    # every entry records both sides of the comparison the gate needs
    assert all("tuned_ms" in e and "default_ms" in e
               for e in doc["entries"].values())
    # the table is tagged for invalidation against the current sources
    from repro.core import autotune
    assert doc["kernel_hash"] == autotune.kernel_signature_hash()


@pytest.mark.slow
def test_regression_gate_fails_on_stale_tuned_table(tmp_path):
    """A committed table whose kernel hash mismatches the sources must fail
    the gate (the satellite staleness check) with an actionable message."""
    tdir = tmp_path / "tables"
    tdir.mkdir()
    (tdir / "stale.json").write_text(json.dumps({
        "version": 1, "backend": "cpu", "impl": "pallas",
        "kernel_hash": "deadbeef0000",
        "entries": {"gemm|m10|c8|k8|float32|ep:none":
                    {"config": {"bk": 8}}},
    }))
    bench = os.path.join(REPO, "BENCH_9.json")
    r = _run("benchmarks.check_regression", "--baseline", bench,
             "--candidate", bench,
             env_extra={"REPRO_TUNED_TABLES_DIR": str(tdir)})
    assert r.returncode != 0
    assert "stale tuned table" in r.stdout
    assert "deadbeef0000" in r.stdout
    # --skip-stale-check restores the pass (same candidate, same baseline)
    r = _run("benchmarks.check_regression", "--baseline", bench,
             "--candidate", bench, "--skip-stale-check",
             env_extra={"REPRO_TUNED_TABLES_DIR": str(tdir)})
    assert r.returncode == 0, r.stdout + r.stderr
