"""The comparison has been shown to fail.

The control puts the reference in the program's place with a stated
guarantee broken (``compare.py``): ``lossy``, a store that acknowledged
every import and lost one column in sixteen, so that no answer is exact
(``stale``, for a mix that writes, is in ``test_ingest.py``).
It has to come out as not correct while the program's own answers in the
same run stay sound.  And with the timed path itself broken underneath
(every Count one too high, ``broken_child.py``) the run's own verdict is
not correct.
"""

import os

import pytest

from test_rehearsal import CELLS, rehearse

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(capfd, workload):
    rc, line, err = rehearse(capfd, "--workload", workload, "--trace", "0", "--control", "lossy")
    c = line["compared"]
    assert c["control_mismatches"][0] > 0, err[-3000:]
    assert c["read_mismatches"][0] == 0, err[-3000:]
    assert line["correct"] is False


def test_broken_timed_path_is_not_correct(capfd):
    rc, line, err = rehearse(capfd, "--workload", "taxi.dashboard-c32", "--trace", "0",
                             child_script=os.path.join(HERE, "broken_child.py"))
    assert line["compared"]["read_mismatches"][0] > 0, err[-3000:]
    assert line["correct"] is False
