"""Device milliseconds of the Mosaic flash-attention calls in one train
step. The program gives its kernels no stable name yet, so the calls are
found by what they are: custom calls to ``tpu_custom_call``. With full
rematerialization the forward kernel runs twice a dense layer; the cells
run the XLA backward, which is not a kernel and is not counted."""

KERNEL = r"custom_call_target=\"tpu_custom_call\"|custom-call\("


def per_step(red):
    """-> (kernel seconds a step, kernel calls a step)."""
    steps = len(red.module_runs(r"jit_step"))
    if not steps:
        return None, 0
    return (red.seconds_matching(KERNEL) / steps,
            red.count_matching(KERNEL) / steps)


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "train":
        return None
    seconds, _ = per_step(red)
    return None if not seconds else seconds * 1e3
