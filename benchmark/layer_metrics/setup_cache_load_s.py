"""`setup_cache_load_s`: seconds reading, deserialising and loading executables
from the persistent compile cache: the summed duration of the compile log's
`build` records before the window whose `cache` is `hit` (`setup_time.py`)."""
import setup_time

UNIT = "s"


def read(run: dict):
    return setup_time.read(run, "cache_load_s")
