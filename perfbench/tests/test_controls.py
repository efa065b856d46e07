"""The control of each kind of cell comes out as not correct through the
harness's own comparison: a whole run (all but the look for a chip) with
``--control``, which puts the reference computed in int8 (the nearest
precision below the bf16 the configurations state) in the program's
place, reads ``correct`` false on one of the cell's numbers. Rehearsal
sizes, CPU; the readings at the cells' own sizes are in PERF.md."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as bench   # noqa: E402

CELLS = {"mistral-7b-v0.3-l16.chat-open": "4",
         "mistral-7b-v0.3-l16.classify-closed": "4",
         "internlm2-1.8b-l4.pretrain-4k": "2"}


def drive(cell, control):
    code, result = bench.execute(
        ["--workload", cell, "--seed", "3000000011", "--seconds",
         CELLS[cell], "--rehearse", "1", "--control", control])
    assert code == bench.REHEARSAL_EXIT
    return result


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_int8_control_is_not_correct(cell):
    result = drive(cell, "int8")
    assert not result["correct"]
    over = [k for k, c in result["checks"].items()
            if c["value"] > c["limit"]]
    assert over and set(over) <= {"gap_max", "gap_mean", "grad_gap",
                                  "delta_gap"}, result["checks"]


def test_half_batch_in_the_references_place_is_not_correct():
    result = drive("internlm2-1.8b-l4.pretrain-4k", "half_batch")
    assert not result["correct"]
    assert result["checks"]["grad_gap"]["value"] \
        > result["checks"]["grad_gap"]["limit"]
