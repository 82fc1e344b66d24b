"""`setup_lower_s`: seconds lowering those programs' jaxprs to MLIR modules:
the union of the compile log's `lower` records that had ended when the
window opened (`setup_time.py`)."""
import setup_time

UNIT = "s"


def read(run: dict):
    return setup_time.read(run, "lower_s")
