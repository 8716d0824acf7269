"""CPU test of the reader of ``store_recycle_share`` on hand-made
windows: the share of the window's store puts served from the in-proc
store's free list, and nothing where the program's round outcomes carry
no such counters."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import spec  # noqa: E402
from chipbench.harness import Ctx  # noqa: E402


def _ctx(outcomes):
    rounds = [{"t_open": 0.0, "t_close": 1.0, "cohort": [], "trace": None,
               "outcome": o} for o in outcomes]
    return Ctx(n=1000, update_dtype="float32", rounds=rounds, window_s=2.0,
               t0=10.0, t_last=12.0, sent={}, updates_per_s=12.0,
               device_kind="TPU v5 lite")


def _out(puts, recycled):
    return SimpleNamespace(accepted=16, skipped=[], store_puts=puts,
                           store_recycled=recycled)


def _read(ctx):
    return spec.metric_reader("store_recycle_share")(ctx)


def test_share_of_recycled_puts_over_the_window():
    ctx = _ctx([_out(18, 18), _out(18, 16), _out(20, 17)])
    assert _read(ctx) == pytest.approx(100.0 * 51 / 56)


@pytest.mark.parametrize("outcomes", [
    [SimpleNamespace(accepted=16, skipped=[])] * 2,   # a program without
    [_out(18, 18), SimpleNamespace(accepted=16, skipped=[])],  # the fields
    [],                                               # an empty window
    [_out(0, 0)],                                     # no put at all
], ids=["parent", "mixed", "empty", "no-puts"])
def test_nothing_where_there_is_nothing_to_read(outcomes):
    assert _read(_ctx(outcomes)) is None


@pytest.mark.parametrize("cell", ["r18-backlog", "r152-backlog"])
def test_share_is_read_in_both_backlog_cells(cell):
    units = {m.name: m.unit for m in spec.load_cell(cell).per_layer}
    assert units["store_recycle_share"] == "%"
