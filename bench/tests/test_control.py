"""Each configuration's controls (the reference in a lowered precision,
in the program's place) read above the configuration's limit, at a tiny
size on the CPU; the program itself reads within it."""
import pytest

from bench import control, harness

from .conftest import make_checkout

CLOSED = {"kind": "closed", "outstanding": 32}


@pytest.mark.parametrize("base", ["sift1m-mcam3-l2-d2d",
                                  "glove100-mcam3-dot"])
def test_controls_fail_and_the_program_passes(tmp_path, jax_cache, base):
    root = make_checkout(str(tmp_path), {"t.closed": (base, CLOSED)})
    cell = harness.load_cell("t.closed", root)
    limit = cell.config["limits"]["answer_gap"]
    r = control.readings(cell, 5, 1.0, cell.config["controls"],
                         allow_cpu=True)
    assert r["program"] <= limit
    assert r["answered"] > 100
    gaps = {c: v["gap"] for c, v in r["control"].items()}
    assert max(gaps.values()) > limit, gaps
    # the bf16 quantizer moves query codes: a real lowering on both paths
    assert gaps["bf16_quant"] > limit
    if "int4" in gaps:
        # 3-bit codes are exact in int4: that cast lowers nothing
        assert gaps["int4"] == 0.0
