"""setup_s: process start to the first timed event (host clock): runtime
creation, warm-up compile of the cell's bucket, and the warm pass."""


def read(run):
    return run.setup_s
