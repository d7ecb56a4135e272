"""Device milliseconds of one fused decode step under the scope
``attn.latent``: the latent projection and its norm and RoPE, the absorption of k_up into the query and v_up behind the weighted sum. None where the program has no such scope."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    got = scopes.program_seconds(ctx, r"decode_impl")
    if got is None or "attn.latent" not in got["seconds"]:
        return None
    steps = got["runs"] * int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * got["seconds"]["attn.latent"] / steps
