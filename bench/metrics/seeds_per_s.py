"""Training seeds the trainers processed in the window (steps x trainers
x batch), over all the window's time on the host clock."""


def read(run):
    return run.seeds / run.window_s
