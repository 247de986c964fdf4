"""Row cache: (touched - reads) / touched over the window, from the program's
counters (rows needed, and unique rows fetched from storage)."""


def read(run):
    touched = float(run.touched.sum())
    return 100.0 * (touched - float(run.reads.sum())) / touched if touched > 0 else None
