"""The control, the reference one precision step below the configuration
(three bfloat16 passes), put in the search's place, comes out not correct
in every cell."""
import pytest

import tiny


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_control_is_not_correct(cell):
    res = tiny.run(cell, control=True)
    gap = res["checks"]["dist_gap"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]
    # the control's ids are exact: only the precision of its distances
    # sets it apart
    assert res["checks"]["recall_at_10"]["value"] > 0.99
