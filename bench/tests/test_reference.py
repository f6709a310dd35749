"""The plain reference agrees with the program's simulator lanes (single,
grid, streamed) and stream generator at small sizes, on the CPU."""

import json

import numpy as np
import pytest

from bench import reference as ref
from bench.run import ROOT
from bench.workloads import Deployment


@pytest.fixture(scope="module")
def mtwnd():
    return Deployment(json.loads((ROOT / "bench/configs/mtwnd.json")
                                 .read_text()))


@pytest.fixture(scope="module")
def candle():
    return Deployment(json.loads((ROOT / "bench/configs/candle.json")
                                 .read_text()))


@pytest.mark.parametrize("seed", [0, 31, 2**31 - 2])
def test_stream_matches_generator(mtwnd, seed):
    wl = mtwnd.spec(seed).realize(600)
    arr, bat = ref.stream(seed, 600, mtwnd.config["stream"])
    assert np.array_equal(wl.batches, bat)
    assert np.max(np.abs(wl.arrivals - arr)) < 1e-5


@pytest.mark.parametrize("config", [(0, 0, 0), (1, 0, 0), (2, 1, 1),
                                    (0, 3, 4), (5, 0, 0), (8, 10, 12)])
def test_single_lane(mtwnd, config):
    from repro.serving import PoolSimulator

    wl = mtwnd.spec(7).realize(600)
    prog = PoolSimulator(mtwnd.profile, mtwnd.types, wl).qos(config).rates
    arr, svc = mtwnd.ref_stream(7, 600)
    assert round(prog * 600) == mtwnd.ref_count(arr, svc, config)


def test_grid_lane(candle):
    ev = candle.evaluator(3)
    n = ev.workload.n_queries
    lattice = candle.space.enumerate()
    pick = lattice[np.random.default_rng(0).choice(len(lattice), 24,
                                                   replace=False)]
    factors = (0.8, 1.0, 1.5)
    grid = ev.grid(pick, factors)
    arr, svc = candle.ref_stream(3, n)
    for w, f in enumerate(factors):
        for b, cfg in enumerate(pick):
            want = candle.ref_count(candle.ref_scaled(arr, f), svc, cfg)
            assert round(grid[w, b] * n) == want, (f, cfg)


def test_streamed_lane(mtwnd):
    from repro.serving import StreamingSimulator

    n = 3 * 4096 + 100           # a partial last chunk too
    res = StreamingSimulator(mtwnd.profile, mtwnd.types,
                             mtwnd.spec(11, 1.25)).qos((5, 1, 1), n)
    arr, svc = mtwnd.ref_stream(11, n, 1.25)
    assert round(res.rate * n) == mtwnd.ref_count(arr, svc, (5, 1, 1))


def test_bfloat16_control_departs(mtwnd):
    """The control (the reference in bfloat16) is far from the float64
    reference on the same stream and pool, at the cells' 1500 queries: its
    arrivals stall once their spacing falls below bfloat16's step."""
    arr, svc = mtwnd.ref_stream(7, 1500)
    a16, s16 = mtwnd.ref_stream(7, 1500, prec=ref.BF16)
    assert np.all(np.isfinite(a16))
    want = mtwnd.ref_count(arr, svc, (5, 1, 1))
    got = mtwnd.ref_count(a16, s16, (5, 1, 1), ref.BF16)
    assert abs(got - want) > 50
