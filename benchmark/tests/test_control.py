"""The control of ``city10000.certified`` (solves stopped at a gate
loosened tenfold) reads beyond the cell's limits; sound solves read
within them.  A small CPU size; the chip readings at the cell's own size
are in ``benchmark/limits/city10000.certified.json``."""

import json

from benchmark import readings, run as R

SMALL = dict(poses=3000, edges=6199, loop_closures=3200, agents=8)


def _numbers(capsys, control):
    capsys.readouterr()
    readings.main(["--workload", "city10000.certified", "--seeds",
                   "2147483748", "--control", str(control)],
                  platform="cpu", config_override=SMALL)
    return [json.loads(line)["numbers"]
            for line in capsys.readouterr().out.strip().splitlines()]


def test_control_fails_and_sound_passes(capsys):
    limits = R.load_json(R.HERE, "limits",
                         "city10000.certified.json")["numbers"]
    for nums in _numbers(capsys, 0):
        assert nums["grad_X"] <= limits["grad_X"]["limit"]
        assert nums["grad_T"] <= limits["grad_T"]["limit"]
    for nums in _numbers(capsys, 1):
        assert nums["grad_X"] > limits["grad_X"]["limit"]
