"""`setup_trace_s`: seconds inside JAX's tracing of the programs that set-up
builds: the union of the compile log's `trace` records that had ended when
the window opened (an inner jit's trace lies inside its caller's, so a sum
would count it twice).  `setup_time.py` has the cut and the reduction."""
import setup_time

UNIT = "s"


def read(run: dict):
    return setup_time.read(run, "trace_s")
