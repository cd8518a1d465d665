"""A sound device-resident run of the harness on the CPU (``correct`` true)
and the same run with the timed path broken underneath (``correct``
false)."""
from bench.tests import tiny


def test_resident_sound_and_broken_runs():
    tiny.check_sound_and_broken("tiny-yi-resident.json")
