"""``kv_view_columns_read_pct`` (ISSUE 39; the counters are PR 38's): of
the ordered pool's table columns, the share that the paged gather reads
gathered, from the engine's two counters differenced over the window."""

import pytest

from benchmark import harness
from test_benchmark_contract import SOME_SERVE_CELLS, check_declared_for_some

NAME = "kv_view_columns_read_pct"
# the engine's stats when the window opens and when it closes: the deltas
# are those of phi's traced run of PR 38 (PERF.md section 6: 66.92%)
OPEN = {"kv_view_columns_read": 700, "kv_view_columns_full": 1000,
        "harvests": 1}
CLOSE = {"kv_view_columns_read": 700 + 107730496,
         "kv_view_columns_full": 1000 + 160989184, "harvests": 9}


def ctx(**over):
    return {"kind": "serve", "stats0": OPEN, "stats1": CLOSE, **over}


def read(c):
    return harness.load_reader(NAME)(c)


def test_reader_gives_the_counters_quotient_over_the_window():
    # what was counted before the window opened is not the window's
    assert read(ctx()) == pytest.approx(100.0 * 107730496 / 160989184)
    # a program that read every table whole reads 100, never more
    whole = {"kv_view_columns_read": 64, "kv_view_columns_full": 64}
    assert read(ctx(stats0=dict.fromkeys(whole, 0), stats1=whole)) == 100.0


@pytest.mark.parametrize("case, over", [
    ("a_train_cell", {"kind": "train"}),
    # the parent of PR 38, or the stats fetch failed at one end
    ("stats_without_the_counters", {"stats0": {"harvests": 1},
                                    "stats1": {"harvests": 9}}),
    ("one_end_without_them", {"stats0": {}}),
    # no layer reads by the rule: both counters stay where they were
    ("a_zero_denominator", {"stats0": {"kv_view_columns_read": 0,
                                       "kv_view_columns_full": 0},
                            "stats1": {"kv_view_columns_read": 0,
                                       "kv_view_columns_full": 0}}),
])
def test_reader_reads_nothing_where_there_is_nothing(case, over):
    assert read(ctx(**over)) is None


def test_the_metric_is_declared_for_the_serve_cells_that_read_by_the_rule():
    # the four cells of ISSUE 39 are listed and trinity's is not; a later
    # PR's serve cell lists it or not, as its reader finds something to read
    # (``test_benchmark_families.py`` holds a root with such a cell to this)
    check_declared_for_some(NAME, **SOME_SERVE_CELLS[NAME])
