"""``moe_rows_computed_pct`` (ISSUE 41): of the router's pair rows, the
share that the held experts' grouped products were handed, from the
engine's two counters differenced over the window."""

import pytest

from benchmark import harness
from test_benchmark_contract import check_declared_for_some, check_moves

NAME = "moe_rows_computed_pct"
# 10 steps of 6 routed layers x 64 slots x 8 picks, the products handed the
# ladder's first step, 64 rows, in every layer of every step
OPEN = {"moe_picks": 3072, "moe_rows_computed": 384, "decode_steps": 1}
CLOSE = {"moe_picks": 3072 + 10 * 6 * 512,
         "moe_rows_computed": 384 + 10 * 6 * 64, "decode_steps": 11}


def ctx(**over):
    return {"kind": "serve", "stats0": OPEN, "stats1": CLOSE, **over}


def read(c):
    return harness.load_reader(NAME)(c)


def test_reader_gives_the_counters_quotient_over_the_window():
    # what was counted before the window opened is not the window's
    assert read(ctx()) == 12.5
    # one layer of one step in ten took the second step: 128 rows for 64
    further = dict(CLOSE, moe_rows_computed=CLOSE["moe_rows_computed"] + 64)
    assert read(ctx(stats1=further)) == pytest.approx(
        100.0 * (60 * 64 + 64) / (60 * 512))
    # a call with no ladder hands the products every pair row: never more
    whole = {"moe_picks": 640, "moe_rows_computed": 640}
    assert read(ctx(stats0=dict.fromkeys(whole, 0), stats1=whole)) == 100.0


@pytest.mark.parametrize("case, over", [
    ("a_train_cell", {"kind": "train"}),
    # every expert held (kanana's engine), or the parent of PR 41
    ("stats_without_the_counter", {
        "stats0": {"moe_picks": 0}, "stats1": {"moe_picks": 512}}),
    ("one_end_without_it", {"stats0": {}}),
    ("a_window_without_a_step", {"stats1": OPEN}),
    ("no_stats_at_all", {"stats0": None, "stats1": None}),
])
def test_reader_reads_nothing_where_there_is_nothing(case, over):
    assert read(ctx(**over)) is None


def test_the_metric_is_declared_for_the_cells_that_hold_a_share():
    # the cells that report ``moe_held_pick_share_pct``; kanana's engine
    # holds every expert and has no such counter; a later PR's serve cell
    # lists it or not, as its reader finds something to read
    m = check_declared_for_some(
        NAME, cells=("trinity-large-preview.serve-full",
                     "mimo-v2.5.serve-full"),
        but=("kanana-2-30b-a3b.serve-full", "rudalle-xl.serve-full",
             "dalle-12b.serve-full",
             "phi-4-mini-flash-reasoning.serve-full"),
        unit="%", better="lower", source="program_counter",
        layer="decode math", moves="tpot_ms")
    held = next(x for x in harness.load_benchmark()["per_layer"]
                if x["name"] == "moe_held_pick_share_pct")
    assert set(held["workloads"]) <= set(m["workloads"])
    check_moves(NAME)
