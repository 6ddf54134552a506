"""Remote feature bytes fetched per step, summed over trainers: misses
plus admissions, times the row's float32 bytes, from the program's exact
streams over the window."""


def read(run):
    rows = sum(sum(lg.comm_volume) for lg in run.logs)
    return rows * run.shapes.feature_dim * 4 / 1e6 / run.steps
