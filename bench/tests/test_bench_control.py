"""The controls, at a size a test run holds: a whole run of each cell
with the configuration's reference, computed one precision step below the
configuration's (float8 for bfloat16), in the program's place comes out
not correct, where the same run of the program comes out correct."""
import pytest

import small_cells


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_launch_control_fails_the_limit(seed):
    c = small_cells.cell("launch-16k-repeat")
    assert small_cells.run(c, seed=seed)["correct"]
    r = small_cells.run(c, seed=seed, control=True)
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_abs_err"]["value"] > \
        r["checks"]["max_abs_err"]["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fails_the_limit_the_program_passes(seed):
    # four layers and some 300 checked tokens: the control's rounding
    # compounds over depth, and the widest gap grows with the tokens read
    c = small_cells.cell("qwen3-14b-chat")
    c.config = dict(c.config, num_hidden_layers=4, check={"requests": 1000})
    c.traffic = dict(c.traffic,
                     output=dict(median=40, sigma=0.3, min=20, max=60))
    assert small_cells.run(c, seed=seed)["correct"]
    r = small_cells.run(c, seed=seed, control=True)
    assert not r["correct"], r["checks"]
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]
