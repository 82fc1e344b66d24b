"""`setup_compile_s`: seconds inside the backend's compile (XLA and Mosaic):
the summed duration of the compile log's `build` records before the window
whose `cache` is `miss` (compiled and stored) or `unstored` (compiled, too
quick to keep or the cache off) (`setup_time.py`)."""
import setup_time

UNIT = "s"


def read(run: dict):
    return setup_time.read(run, "compile_s")
