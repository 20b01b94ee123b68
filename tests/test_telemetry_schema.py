"""Fast artifact lint (tier-1): every committed BENCH_*.json round
artifact (tests/data/bench_rounds/ holds fixtures of each shape) — and
any telemetry JSONL the tree carries — must validate
against the versioned schemas in obs/schema.py, via the same
tools/check_telemetry_schema.py entry point CI and humans run."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "check_telemetry_schema.py")

sys.path.insert(0, os.path.join(REPO, "tools"))
import check_telemetry_schema as lint  # noqa: E402


def test_committed_bench_artifacts_validate():
    paths = lint.default_paths()
    assert paths, "expected committed BENCH_*.json artifacts"
    errors = []
    for p in paths:
        errors.extend(lint.check_file(p))
    assert errors == []


def test_tool_cli_exit_codes(tmp_path):
    ok = subprocess.run([sys.executable, TOOL], capture_output=True,
                        text=True, cwd=REPO)
    assert ok.returncode == 0, ok.stderr

    bad = tmp_path / "BENCH_bad.json"
    bad.write_text(json.dumps({"metric": "m", "value": "not-a-number"}))
    r = subprocess.run([sys.executable, TOOL, str(bad)],
                       capture_output=True, text=True)
    assert r.returncode == 1
    assert "value" in r.stderr


def test_tool_accepts_failed_round_wrapper(tmp_path):
    """tests/data/bench_rounds/BENCH_r01.json shape: the driver captured
    a crash (rc != 0,
    parsed null) — a legitimate artifact, not a schema violation."""
    p = tmp_path / "BENCH_failed.json"
    p.write_text(json.dumps({"n": 1, "cmd": "python bench.py", "rc": 1,
                             "tail": "Traceback ...", "parsed": None}))
    assert lint.check_file(str(p)) == []
    # but a wrapper claiming SUCCESS with no payload is an error
    p.write_text(json.dumps({"n": 1, "cmd": "python bench.py", "rc": 0,
                             "tail": "", "parsed": None}))
    assert lint.check_file(str(p)) != []


def test_tool_validates_jsonl(tmp_path):
    from pcg_mpi_solver_tpu.obs.schema import TELEMETRY_SCHEMA

    p = tmp_path / "run.jsonl"
    good = {"schema": TELEMETRY_SCHEMA, "t": 0.0, "kind": "note", "msg": "x"}
    p.write_text(json.dumps(good) + "\n")
    assert lint.check_file(str(p)) == []
    p.write_text(json.dumps({"kind": "note"}) + "\nnot json\n")
    errs = lint.check_file(str(p))
    assert len(errs) >= 2


def test_setup_detail_fields_validate():
    """Warm-path bench fields (cache/ subsystem): numeric-or-null
    setup_s/time_to_first_iter_s and the off/cold/warm setup_cache enum
    are enforced WHEN present; absent fields (pre-warm-path committed
    artifacts) stay valid — exercised above on the fixtures."""
    from pcg_mpi_solver_tpu.obs.schema import validate_bench_line

    base = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0}
    ok = dict(base, detail={"setup_s": 1.5, "setup_cache": "cold",
                            "time_to_first_iter_s": None})
    assert validate_bench_line(ok) == []
    assert validate_bench_line(dict(base, detail={})) == []
    bad_num = dict(base, detail={"setup_s": "1.5s"})
    assert any("setup_s" in e for e in validate_bench_line(bad_num))
    bad_ttfi = dict(base, detail={"time_to_first_iter_s": "soon"})
    assert any("time_to_first_iter_s" in e
               for e in validate_bench_line(bad_ttfi))
    bad_enum = dict(base, detail={"setup_cache": "lukewarm"})
    assert any("setup_cache" in e for e in validate_bench_line(bad_enum))


def test_current_bench_line_is_schema_valid():
    """The line bench.py emits TODAY must satisfy the schema the lint
    enforces (catches drift between emitter and validator)."""
    from pcg_mpi_solver_tpu.bench import _error_line
    from pcg_mpi_solver_tpu.obs.schema import validate_bench_line

    assert validate_bench_line(json.loads(_error_line("x"))) == []
