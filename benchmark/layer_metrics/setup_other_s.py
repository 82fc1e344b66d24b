"""`setup_other_s`: what is left of the interval from the compile log's
`installed_at` to the window's open once the union of every record is taken
out: the backend's start, weights and transfers, the executed followed and
warm-up updates, Python.  With that union it closes the interval exactly
(`setup_time.py`); the imports before `installed_at` are no part of it."""
import setup_time

UNIT = "s"


def read(run: dict):
    return setup_time.read(run, "other_s")
