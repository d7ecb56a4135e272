"""Backend compiles inside the measured window of a training cell
(the compile listener): anything but 0 means a shape was not warmed."""


def read(ctx):
    return ctx["compiles_in_window"] if ctx["kind"] == "train" else None
