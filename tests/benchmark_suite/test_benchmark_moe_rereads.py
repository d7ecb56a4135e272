"""``moe_expert_rereads_pct`` (ISSUE 43): of the touched experts' reads,
the share that the row tiles read again, from the engine's two counters
differenced over the window."""

import pytest

from benchmark import harness
from test_benchmark_contract import check_declared_for_some, check_moves

NAME = "moe_expert_rereads_pct"
# 10 steps of 8 routed layers: 63 of 64 experts touched a layer, and of the
# three boundaries of four tiles, three straddled
OPEN = {"moe_experts_touched": 504, "moe_group_reads": 528, "decode_steps": 1}
CLOSE = {"moe_experts_touched": 504 + 10 * 8 * 63,
         "moe_group_reads": 528 + 10 * 8 * 66, "decode_steps": 11}


def ctx(**over):
    return {"kind": "serve", "stats0": OPEN, "stats1": CLOSE, **over}


def read(c):
    return harness.load_reader(NAME)(c)


def test_reader_gives_the_re_reads_over_the_touched_of_the_window():
    # what was counted before the window opened is not the window's
    assert read(ctx()) == pytest.approx(100.0 * 3 / 63)
    # one tile: every touched expert read once
    one = dict(CLOSE, moe_group_reads=OPEN["moe_group_reads"] + 10 * 8 * 63)
    assert read(ctx(stats1=one)) == 0.0
    # every boundary of three tiles straddled, 99 of 128 touched
    three = {"moe_experts_touched": 6 * 99, "moe_group_reads": 6 * 101}
    assert read(ctx(stats0=dict.fromkeys(three, 0), stats1=three)) == \
        pytest.approx(100.0 * 2 / 99)


@pytest.mark.parametrize("case, over", [
    ("a_train_cell", {"kind": "train"}),
    # a program from before the tiles (the parent of PR 43)
    ("stats_without_the_counter", {
        "stats0": {"moe_experts_touched": 0},
        "stats1": {"moe_experts_touched": 512}}),
    ("one_end_without_it", {"stats0": {}}),
    ("a_dense_engine", {"stats0": {"decode_steps": 1},
                        "stats1": {"decode_steps": 11}}),
    ("a_window_without_a_step", {"stats1": OPEN}),
    ("no_stats_at_all", {"stats0": None, "stats1": None}),
])
def test_reader_reads_nothing_where_there_is_nothing(case, over):
    assert read(ctx(**over)) is None


def test_the_metric_is_declared_for_the_routed_cells():
    # the cells that report ``moe_experts_roofline``, whose denominator a
    # re-read is in; a dense engine has no such counter; a later PR's
    # routed cell lists it, another stays out, and neither edits this test
    m = check_declared_for_some(
        NAME, cells=("kanana-2-30b-a3b.serve-full",
                     "trinity-large-preview.serve-full",
                     "mimo-v2.5.serve-full", "lfm2-24b-a2b.serve-full"),
        but=("rudalle-xl.serve-full", "dalle-12b.serve-full",
             "phi-4-mini-flash-reasoning.serve-full"),
        unit="%", better="lower", source="program_counter",
        layer="decode math", moves="tpot_ms")
    roofline = next(x for x in harness.load_benchmark()["per_layer"]
                    if x["name"] == "moe_experts_roofline")
    assert set(roofline["workloads"]) <= set(m["workloads"])
    check_moves(NAME)
