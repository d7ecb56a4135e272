"""Of the ordered pool's table columns, the share that the paged gather
reads gathered: the engine's ``kv_view_columns_read`` over
``kv_view_columns_full``, both differenced over the whole window (no
capture). 100 would be every layer reading its whole table, which is what
a program without the width rule does; the live columns of a staggered
batch are 51-61%. None where the engine has no such counters and where no
layer reads by the rule (both stay 0: a quotient is never a reduction
that the device did not make).

Lower is better only at a FIXED number of slot groups (the counter
``kv_view_groups``, read beside it): more groups gather fewer columns and
pay a further gather each, and kanana's cell read 74.96% at four groups of
eight slots in LESS time (``decode_kv_view_ms`` 1.712) than 66.9% at eight
groups of four (1.873; chip runs of PR 39 and PR 38). A change that
re-groups is judged by ``decode_kv_view_ms`` + ``decode_attend_ms`` and
``tpot_ms``, not by this share: it says how far the reads are from the live
columns, where the next ``perf_opt`` on the gather starts."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    gathered = scopes.counter_delta(ctx, "kv_view_columns_read")
    full = scopes.counter_delta(ctx, "kv_view_columns_full")
    if gathered is None or not full:
        return None
    return 100.0 * gathered / full
