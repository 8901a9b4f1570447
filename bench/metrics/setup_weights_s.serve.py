"""Set-up time spent making the weights from the seed: the one jitted
call of `benchlib.lm_weights` to its result on the device (its compile
or cache read included), host clock, in s."""


def read(ctx):
    return ctx["inputs"].get("setup_weights_s")
