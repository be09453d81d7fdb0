"""The control: the reference put in the program's place and computed in
bfloat16, the nearest precision below the float32 the configurations
state.  It has to come out as not correct wherever a window counts more
than 256 events (bfloat16 holds 8 bits of a count), which every cell's
windows do; ``control.py`` reads the same on the chip at the cells' own
sizes."""
import io
import json
import os

import numpy as np
import pytest

from benchmarks.harness import check
from benchmarks.tests import control
from benchmarks.tests.conftest import ROOT, with_parked


@pytest.mark.parametrize("config", ["nexmark_q5", "ysb",
                                    "nexmark_q5_mesh4"])
@pytest.mark.parametrize("seed", [1, 2_147_483_700, 77])
def test_control_fails_at_the_cells_own_windows(manifest, config, seed):
    conf = next(c for c in with_parked(manifest)["configs"]
                if c["name"] == config)
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    # the cell's own window and key count, over a shorter stream and pool
    cfg["pool_rows"] = 4 * cfg["slide_events"]
    n_events = 3 * cfg["win_events"] + 12345
    numbers, sound = control.control_numbers(config, cfg, seed, n_events)
    assert check.verdict(sound, io.StringIO())[0]
    assert not check.verdict(numbers, io.StringIO())[0]
    assert numbers["rows_wrong"] > 0.1 * len(
        control.load_pipeline(config).reference(cfg, seed, n_events)[0])
    assert numbers["rows_missing"] == numbers["rows_unexpected"] == 0
    if config != "ysb":
        # the hot auction's count is far over 256: every full window's fold
        assert numbers["folds_wrong"] > 0 and sound["folds_wrong"] == 0


def test_bfloat16_is_exact_below_256_and_not_above():
    import ml_dtypes
    keys = np.zeros(1 << 12, np.int64)
    _, _, small, _ = check.sliding_counts(keys, None, 1 << 12, 256, 128, 1,
                                          ml_dtypes.bfloat16)
    assert (small[:-1] == 256).all()
    _, _, big, _ = check.sliding_counts(keys, None, 1 << 12, 518, 259, 1,
                                        ml_dtypes.bfloat16)
    assert (big[:5] != 518).all()
