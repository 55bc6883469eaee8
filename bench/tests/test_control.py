"""The control, the reference in the precision one step below the
configuration's, fails the check that the program passes: the control
script at test size, on three seeds, for both loops."""

import pytest

from bench import control, harness


@pytest.mark.parametrize("cell", ["tiny-rw64.batch", "tiny-rw64.serve"])
def test_control_fails_where_the_program_passes(tiny_root, cell):
    out = control.main(["--workload", cell, "--seeds", "1", "2", "3",
                        "--seconds", "0.3"], root=tiny_root,
                       require_tpu=False)
    limits = harness.load_cell(cell, root=tiny_root).config["checks"]
    assert all(out[n]["program_max"] <= limits[n] for n in limits)
    assert any(out[n]["control_min"] > limits[n] for n in limits)
