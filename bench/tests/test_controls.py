"""Each cell's control, put in the program's place, makes a whole run
report `correct` false (see `bench/harness/controls.py`)."""
from __future__ import annotations

import pytest

from bench.harness import controls

from .tiny import run_tiny

# bf16_accumulate lowers the precision of the MXU's GF(2^8) sums where the
# cell's coding runs on gf_bitmatmul: the encode of every save, the set-up
# save of a restore cell too; an XOR fold has no precision to lower, so
# drop_source breaks the bit-exact repair instead.
CONTROLS = {"wide-save": "bf16_accumulate",
            "wide-restore-1down": "drop_source",
            "wide-restore-cluster-down": "bf16_accumulate",
            "wide-restore-healthy": "bf16_accumulate"}


@pytest.mark.parametrize(("cell_name", "control"), sorted(CONTROLS.items()))
def test_the_control_is_not_correct(cell_name, control):
    out = run_tiny(cell_name, backend=controls.control(control))
    assert out["correct"] is False, out["checks"]


def test_the_plain_product_matches_the_reference():
    """At float32 the control's bit-plane product is the reference's GF
    product, so it is the lowered precision alone that fails a run."""
    import numpy as np

    from bench.harness import reference
    rng = np.random.default_rng(0)
    A, _ = reference.unilrc(2, 10)
    data = rng.integers(0, 256, (A.shape[1], 256), dtype=np.uint8)
    expect = reference.encode_columns(A, data)
    assert np.array_equal(controls.gf_product(A, data, mantissa_bits=23),
                          expect)
    assert not np.array_equal(controls.gf_product(A, data), expect)
