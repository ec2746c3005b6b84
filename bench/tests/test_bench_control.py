"""The control: the reference computed with float8 matmul operands (the
next precision below the configuration's bfloat16), put in the
program's place, and judged by the comparison a run makes
(``train_cell.gaps`` and ``train_cell.judge``) against the
configuration's own ``limits.train``.  It must come out not correct
where the bfloat16 program comes out correct, and so must a step that
leaves half the batch out.  This is the tiny configuration on the CPU,
driven end to end through ``calibrate.readings``; at the cell's own size
the readings come from ``bench/calibrate.py --stand-ins`` on the chip."""
import os

import jax
import pytest

from bench import calibrate, harness, train_cell
from bench.tests import tiny


def _verdict(readings, limits):
    return train_cell.judge(readings, limits)[1]


@pytest.mark.parametrize("seed", [3, 4])
def test_control_fails_where_the_program_passes(seed, tmp_path):
    root = str(tmp_path)
    cell = harness.Cell(tiny.write(root, config=tiny.BF16_CONFIG),
                        tiny.CELL, root=root)
    limits = cell.config["limits"]["train"]
    with open(os.devnull, "w") as sink:
        (row,) = list(calibrate.readings(cell, jax.devices()[:1], [seed],
                                         stand_ins=True, out=sink))
    assert _verdict(row["program"], limits), row
    assert not _verdict(row["control"], limits), row
    assert not _verdict(row["half_batch"], limits), row

