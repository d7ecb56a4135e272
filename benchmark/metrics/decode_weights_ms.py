"""Device milliseconds of one fused decode step under ``attn.proj``, ``ff``,
``head``, ``embed`` and ``norm``: what streams the weights, and so what the
weights' bandwidth floor speaks to."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    steps = int(ctx["cell"].spec["engine"]["chunk_steps"])
    return scopes.scope_ms(ctx, r"decode_impl", ("attn.proj", "ff", "head", "embed", "norm"), per=steps)
