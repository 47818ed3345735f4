"""Share of the traced window in which no operation ran on the chip
(the idlest chip where the cell uses several). Layer: device.
1 - union of the device's op intervals / window, from the xplane."""
from benchmarks.harness import layers


def read(run):
    return layers.idle_share(run)
