"""``moe.slots_held``: the slots a layer's router sent to the experts held,
from the counters the ``afmoe`` adapter already reports."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402


@pytest.mark.parametrize("counters,want", [
    ({"layers": 328, "slots_held": 328 * 32768, "slots_dropped": 0,
      "max_load": 1100}, 32768.0),            # the even share of cell 3
    ({"layers": 4, "slots_held": 49000, "slots_dropped": 0}, 12250.0),
    ({"layers": 0, "slots_held": 0}, None),   # no expert layer ran
    ({"slots_dropped": 0}, None),             # an adapter without the counter
    (None, None),                             # the dense cells: no counters
])
def test_the_reader_divides_the_slots_by_the_layers_or_finds_nothing(
        counters, want):
    read = run.metric_reader("moe.slots_held")
    assert read({"counters": counters, "trace": {}}) == want
    assert read({}) is None
