"""Device ops: losses, activations, reversible blocks, the pallas kernels."""


def pallas_interpret() -> bool:
    """Whether a pallas kernel runs through the interpreter: True on the
    ``cpu`` backend (where the test suite runs), False on ``tpu`` (Mosaic
    compiles it).  Any other backend is an error — nobody wrote these
    kernels for it, and quietly interpreting them there would pass for a
    working accelerator path."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"pallas kernels are written for the tpu backend (interpreted on "
        f"cpu for tests); the default backend is {backend!r}")
