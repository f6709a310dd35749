"""The control, the plain reference computed in bfloat16 (the precision
below the float32 the configurations state), stands in for the program's
answers in a run of each cell at its own size, and fails its limits."""

import pytest

from bench import reference, run
from bench.run import ROOT


@pytest.mark.parametrize("cell", ["mtwnd-search", "candle-sweep",
                                  "mtwnd-stream"])
def test_control_is_not_correct(cell):
    result = run.run_cell(run.Cell.load(ROOT, cell), 31337, 0.0, False,
                          require_chip=False, control=reference.BF16)
    assert result["correct"] is False, result["check"]
    failed = [k for k, c in result["check"].items()
              if not c["value"] <= c["limit"]]
    assert {"mean_gap", "count_gap"} & set(failed)
