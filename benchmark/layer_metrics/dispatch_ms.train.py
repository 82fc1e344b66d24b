"""`dispatch_ms.train`: host time inside the runner's span around each
`Trainer.step` call, summed over the window, per update."""
UNIT = "ms"


def read(run: dict):
    spans = [d for name, _, d in run["spans"] if name == "dispatch"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
