"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and each
of its phases, at a small size, runs and passes its own checks."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_refuses_without_a_tpu(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""                   # no phase line, no result line
    assert "not a TPU" in out.err


def test_search_phase_holds_the_verify_anchor(smoke):
    """On one backend the search, the replay and the GP agree exactly, so
    the diagnosis finds no difference and the anchor holds."""
    _, res = smoke.phase_search()
    assert res["anchor_match"] and tuple(res["best"]) == (4, 0, 1)
    assert res["first_difference"] == "none"
    assert res["gp_mean_max_abs_diff_vs_cpu"] == 0.0


def test_wide_lane_phase_is_bit_identical(smoke):
    _, res = smoke.phase_wide_lanes()
    assert res["batch_row"]["bit_identical"]
    assert res["grid_cell"]["bit_identical"]


def test_stream_phase_agrees_with_realize(smoke):
    _, res = smoke.phase_stream(n_stream=1 << 14, n_prefix=1 << 12)
    assert res["streamed_queries"] == 1 << 14
    assert res["counts_agree"] and res["prefix_arrivals_differ"] == 0


def test_episode_phase_reports_both_events(smoke):
    _, res = smoke.phase_episode(n=300)
    assert [e["kind"] for e in res["events"]] == ["spot_preemption",
                                                  "price_change"]
    json.dumps(res)                        # the phase line is plain JSON


def test_serve_phase_at_smoke_width(smoke):
    _, res = smoke.phase_serve(preset="smoke", budget=3, n_queries=12,
                               interpret=True)
    assert res["samples"] and res["kernel_vs_gather_max_abs"] <= 1e-5
    json.dumps(res)
