"""Layer: coordinate descent. Host seconds of the random-effect dataset
builds in set-up (the program's span ``game_re.build``, one per coordinate,
in the warm-up fit): entity grouping, the active-row cap, the bucket plan
and the blocks laid out on the device."""


def read(ctx):
    return ctx["state"].clocks.get("re_dataset_build_s")
