"""Share of the traced slice in which no operation ran on the device:
1 - union of device-op intervals over the slice. In percent."""

import xplane


def read(run):
    return None if run["cell"].rehearsal else xplane.idle_share_percent(run.get("trace"))
