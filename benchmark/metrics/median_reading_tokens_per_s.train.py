"""Tokens per second of the MEDIAN reading (a reading = the mix's
``steps_per_reading`` steps ending in one fetch): what the device
sustains between the host's pauses. ``train_tokens_per_s`` is every
token over the whole window; the two differ by what stalls cost."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return ctx["readings"]["median_of_readings_tokens_per_s"]
