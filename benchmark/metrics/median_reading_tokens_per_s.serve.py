"""Tokens per second of the MEDIAN harvest (all streams' deliveries of
one harvest over the seconds since the previous one): the rate between
admissions. ``images_per_s`` is every token over the whole window, so it
lies below this by what admissions and pauses stretch."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return ctx["readings"]["median_of_readings_tokens_per_s"]
