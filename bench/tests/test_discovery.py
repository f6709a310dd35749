"""A configuration, traffic mixes, a kind of request and a metric that a
later change adds as new files (with entries in BENCHMARK.json) are found
by name, and no file that was already there needs an edit."""

import hashlib
import json
import shutil

from bench import run
from bench.run import ROOT

FIX = ROOT / "bench" / "tests" / "fixtures"


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    before = _digests(tmp_path)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, entries in json.loads((FIX / "entries.json").read_text()
                                   ).items():
        spec[key] = spec[key] + entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for sub in ("configs", "traffic", "metrics", "kinds"):
        for f in (FIX / sub).iterdir():
            assert not (tmp_path / "bench" / sub / f.name).exists()
            shutil.copy(f, tmp_path / "bench" / sub / f.name)

    cell = run.Cell.load(tmp_path, "tiny.sweep")
    assert cell.config["name"] == "tiny"
    assert cell.traffic["load_factors"] == [1.0, 1.5]
    result = run.run_cell(cell, 5, 0.0, False, root=tmp_path,
                          require_chip=False)
    assert result["metrics"]["dummy.units"] == {"value": 1, "unit": "units"}
    assert "scored_queries_per_s" not in result["metrics"]
    assert result["correct"], result["check"]

    # A new kind of request, in a file of its own.
    result = run.run_cell(run.Cell.load(tmp_path, "tiny.batch"), 6, 0.0,
                          False, root=tmp_path, require_chip=False)
    assert result["metrics"]["dummy.units"] == {"value": 1, "unit": "units"}
    assert result["correct"], result["check"]

    after = _digests(tmp_path)
    assert all(after[p] == d for p, d in before.items())
    added = {p.relative_to(FIX) for p in FIX.rglob("*")
             if p.is_file() and p.suffix in (".json", ".py")
             and p.name != "entries.json"}
    assert len(after) == len(before) + len(added) == len(before) + 5
