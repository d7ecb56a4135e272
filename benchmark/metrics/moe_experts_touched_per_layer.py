"""Distinct routed experts that received a pick, a routed layer a decode
step: the engine's ``moe_experts_touched`` counter over ``decode_steps`` x
the cell's routed layers, differenced over the window. What the experts'
products have to read; None where the engine has no such counter."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    touched = scopes.counter_delta(ctx, "moe_experts_touched")
    steps = scopes.counter_delta(ctx, "decode_steps")
    layers = getattr(ctx["dims"], "moe_layers", 0)
    if touched is None or not steps or not layers:
        return None
    return touched / (steps * layers)
