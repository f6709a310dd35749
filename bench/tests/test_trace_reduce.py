"""The trace reduction: device busy union, device time per module and per
layer, and idle gaps by host span."""

import json

import pytest

from bench import trace_reduce as tr
from bench.run import ROOT

LAYERS = tr.load_layers(ROOT / "bench" / "layers")


def test_hand_made_trace():
    ms = 1e6
    events = {
        "devices": {
            "/device:TPU:0": [
                ["jit__grid_lane_qos_counts", 10 * ms, 40 * ms],
                ["jit__spec_chunk", 35 * ms, 50 * ms],      # overlaps
                ["jit_convert_element_type", 70 * ms, 80 * ms],
                ["jit__grid_lane_qos_counts", 95 * ms, 130 * ms],  # clipped
            ],
            "/device:TPU:1": [
                ["jit__grid_lane_qos_counts", 0 * ms, 100 * ms],
            ],
        },
        "host": [[tr.WINDOW, 0.0, 100 * ms],
                 ["sweep", 0.0, 100 * ms],
                 ["sweep.dispatch", 50 * ms, 70 * ms]],
    }
    r = tr.reduce(events, LAYERS)
    assert r["window_s"] == pytest.approx(0.1)
    # Device 0: 10-50, 70-80, 95-100 = 55 ms; device 1: 100 ms.
    assert r["busy_s"] == pytest.approx((0.055 + 0.1) / 2)
    assert r["layer_s"]["scan"] == pytest.approx((0.030 + 0.005 + 0.1) / 2)
    assert r["layer_s"]["workload"] == pytest.approx(0.015 / 2)
    assert r["layer_s"]["other"] == pytest.approx(0.010 / 2)
    # Executions: device 0 ran 1 + 5/35 scans, device 1 one.
    assert r["layer_n"]["scan"] == pytest.approx((1 + 5 / 35 + 1) / 2)
    assert r["device_ops"][0][0] == "jit__grid_lane_qos_counts"
    # Device 0's gaps: 0-10 (sweep), 50-70 (dispatch), 80-95 (sweep).
    assert r["idle_gaps"] == [["sweep.dispatch", pytest.approx(0.02)],
                              ["sweep", pytest.approx(0.015)],
                              ["sweep", pytest.approx(0.01)]]


def test_leading_gap_is_not_idle():
    """The traced stretch opens at its first device event: the gap before
    it is the profiler starting, not the program idling."""
    ms = 1e6
    events = {
        "devices": {"/device:TPU:0": [["jit__stream_chunk", 110 * ms,
                                       150 * ms],
                                      ["jit__stream_chunk", 160 * ms,
                                       200 * ms]]},
        "host": [[tr.WINDOW, 0.0, 200 * ms],
                 ["stream.chunk", 150 * ms, 200 * ms]],
    }
    r = tr.reduce(events, LAYERS)
    assert r["window_s"] == pytest.approx(0.09)
    assert r["busy_s"] == pytest.approx(0.08)
    assert r["idle_gaps"] == [["stream.chunk", pytest.approx(0.01)]]


def test_module_names_and_layers():
    assert tr.module_name("jit__simulate_scan(1234)") == "jit__simulate_scan"
    assert tr.layer_of("jit__stream_chunk", LAYERS) == "scan"
    assert tr.layer_of("jit__spec_chunk", LAYERS) == "workload"
    assert tr.layer_of("jit_gp_posterior", LAYERS) == "search.gp"
    assert tr.layer_of("jit_iota", LAYERS) == "other"


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "host": []}, LAYERS)


def test_recorded_v5e_stream_trace():
    """16 streamed chunks of mtwnd at load 1.25 on one TPU v5e, as
    ``extract`` read them from the profiler (no ``trace.window`` span: the
    window is the device events' extent)."""
    events = json.loads((ROOT / "bench/tests/data/stream_v5e.json")
                        .read_text())
    r = tr.reduce(events, LAYERS)
    assert list(events["devices"]) == ["/device:TPU:0"]
    assert r["layer_n"]["scan"] == 16 and r["layer_n"]["workload"] == 16
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(sum(r["layer_s"].values()), rel=1e-3)
    assert r["device_ops"][0][0] == "jit__stream_chunk"
    # 16 chunks of 4096 steps: about 7.8 us a step on this chip.
    us_per_step = 1e6 * r["layer_s"]["scan"] / (16 * 4096)
    assert 5 < us_per_step < 12
    # Every gap is the host reading the chunk's last arrival back.
    assert {name for name, _ in r["idle_gaps"]} == {"np.asarray(jax.Array)"}
