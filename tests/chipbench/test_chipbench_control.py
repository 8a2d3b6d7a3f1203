"""The control: the reference computed one precision below the program's
bfloat16 (float8 e4m3, per-tensor scale) in the program's place must come
out as not correct under the cell's limits, on every seed tried."""
import jax
import pytest

import tinycell
from chipbench import calibrate, compare


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.make_root(tmp_path_factory.mktemp("control"))


def test_control_is_not_correct(root):
    reads = list(calibrate.readings(root, "tiny-1", [1, 2, 3], "control",
                                    jax.devices()))
    for r in reads:
        ok, checks = compare.verdict(r, tinycell.LIMITS)
        assert ok is False, (r["seed"], checks)
        assert checks["grad_diff_median"]["value"] > \
            checks["grad_diff_median"]["limit"]


def test_program_is_correct(root):
    """The same seeds, the program as the configuration states it."""
    for r in calibrate.readings(root, "tiny-1", [1, 2, 3], "program",
                                jax.devices()):
        ok, checks = compare.verdict(r, tinycell.LIMITS)
        assert ok is True, (r["seed"], checks)
