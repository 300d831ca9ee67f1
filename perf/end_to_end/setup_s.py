"""Process start to the window's opening: model directory, server
start-up (weights, KV pool), compile or cache load, warm-up, canary."""


def read(run):
    return run.window.t0 - run.t_start
