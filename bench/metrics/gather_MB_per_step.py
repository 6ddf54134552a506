"""Feature bytes the training step's programs gather from the device
table per step, summed over trainers: the program's
``train.rows_gathered`` counter (the ids of every trainer's blocks, each
hop's and the seeds') over the traced window, times the row's float32
bytes, in MB per step. None untraced or where the program counts none."""


def read(run):
    if run.session is None or "train.rows_gathered" not in run.session.registry:
        return None
    rows = run.session.registry["train.rows_gathered"].total
    return rows * run.shapes.feature_dim * 4 / 1e6 / run.steps
