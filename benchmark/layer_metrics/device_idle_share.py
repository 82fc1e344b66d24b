"""`device_idle_share`: 1 - union of device-operation intervals over the
traced window, averaged over the chips used."""
UNIT = "%"


def read(run: dict):
    if not run.get("device") or not run["device"]["window_s"]:
        return None
    return 100.0 * (1.0 - run["device"]["busy_s"] / run["device"]["window_s"])
