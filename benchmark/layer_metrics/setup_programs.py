"""`setup_programs`: how many programs the backend built or loaded before the
window opened: the compile log's `build` records (`setup_time.py`)."""
import setup_time

UNIT = "programs"


def read(run: dict):
    return setup_time.read(run, "programs")
