"""The yardstick's arithmetic: contract limits, ticks, trace reducer,
roofline reader, the controls (a reference that breaks exactly-once is not
correct), and the generator copy against the program's."""

import pytest

from selfcheck import check


@pytest.mark.parametrize("name", ["manifest", "ticks", "trace_reducer",
                                  "roofline", "controls", "generator"])
def test_selfcheck(name):
    getattr(check, name)()
