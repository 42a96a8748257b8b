"""The benchmark entry point reports a failed section and exits non-zero."""
from benchmarks.run import run_sections


def _rows():
    return [{"name": "fine", "us_per_call": 1.0, "derived": "ok"}]


def _boom():
    raise RuntimeError("boom")


def test_failed_section_prints_error_row_and_exits_nonzero(capsys):
    rc = run_sections([("raises", _boom), ("runs", _rows)])
    out = capsys.readouterr().out
    assert rc == 1
    assert '"ERROR: RuntimeError: boom"' in out
    assert "fine,1.0" in out                 # later sections still run


def test_all_sections_passing_exits_zero(tmp_path, capsys):
    rc = run_sections([("runs", _rows)], str(tmp_path / "rows.json"))
    assert rc == 0
    assert (tmp_path / "rows.json").read_text().count('"fine"') == 1
