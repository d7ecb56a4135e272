"""What holding the window layers to their window saves of the cache: 1 -
(layer-pages in use) / (layer-pages an all-full cache would have in use at
the same positions), from the engine's ``layer_pages_in_use`` and
``layer_pages_all_full`` (the mean of the window's two ends). A slot less
than a window deep saves nothing; one at its sequence's end holds window
+ 1 pages of a window layer where a full layer holds them all. None
where the engine has one pool."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    s0, s1 = ctx.get("stats0") or {}, ctx.get("stats1") or {}
    if "layer_pages_all_full" not in s0 or "layer_pages_all_full" not in s1:
        return None
    whole = s0["layer_pages_all_full"] + s1["layer_pages_all_full"]
    if not whole:
        return None
    held = s0["layer_pages_in_use"] + s1["layer_pages_in_use"]
    return 100.0 * (1.0 - held / whole)
