"""The benchmark runner's exit code reports failed benchmarks."""
import types

import pytest

from benchmarks import run as bench_run


def _mod(fn):
    return types.SimpleNamespace(run=fn)


def _fails(report):
    raise RuntimeError("bench broke")


@pytest.mark.parametrize("mods, import_failed, want", [
    ([("ok", _mod(lambda report: report("ok", 1.0)))], [], 0),
    ([("ok", _mod(lambda report: None)), ("bad", _mod(_fails))], [], 1),
    ([("ok", _mod(lambda report: None))], ["broken_import"], 1),
])
def test_exit_code_counts_failures(monkeypatch, capsys, mods, import_failed,
                                   want):
    monkeypatch.setattr(bench_run, "discover",
                        lambda: (mods, list(import_failed)))
    monkeypatch.setattr(bench_run, "summarize_bench_json", lambda: None)
    monkeypatch.setattr(bench_run.sys, "argv", ["run"])
    assert bench_run.main() == want
    out = capsys.readouterr().out
    for name in import_failed + [n for n, _ in mods if n == "bad"]:
        assert f"{name}_FAILED" in out
