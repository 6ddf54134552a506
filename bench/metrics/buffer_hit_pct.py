"""Share of the sampled unique remote nodes found in the buffer, over all
trainers and steps of the window, from the program's exact streams."""


def read(run):
    remote = sum(sum(lg.unique_remote) for lg in run.logs)
    missed = sum(sum(lg.comm_missed) for lg in run.logs)
    return None if remote == 0 else 100.0 * (remote - missed) / remote
