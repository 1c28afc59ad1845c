"""Process start to the window's opening: weights, warm-up (compile or cache
load of every program the traffic uses) and the ramp."""


def read(run):
    return run.setup_s
