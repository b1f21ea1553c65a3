"""compile.warm_ms: wall time of the program's warm-up compile of the
cell's bucket, as core/compile.py reports it (program span)."""


def read(run):
    return None if run.warm is None else float(run.warm["compile_ms"])
