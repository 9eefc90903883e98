"""Set-up: from process start to the window's opening — imports,
weights, warm-up and the traffic's ramp."""


def read(run):
    return run.setup_time
