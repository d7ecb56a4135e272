"""Host milliseconds inside the prefill (or warm-admission) call until it
returns, a call: the engine's ``admit_prefill_s`` counter over its
``prefill_runs`` + ``warm_admits``, differenced over the whole window."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    seconds = scopes.counter_delta(ctx, "admit_prefill_s")
    calls = sum(filter(None, (scopes.counter_delta(ctx, k)
                              for k in ("prefill_runs", "warm_admits"))))
    return 1e3 * seconds / calls if seconds is not None and calls else None
