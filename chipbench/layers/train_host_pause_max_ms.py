"""The longest pause of the host while the train loop ran: the longest
``host_pause`` event between the first and the last
``train/step_dispatch`` span of the window; 0 when there is none. The
host runs some thirty steps ahead of the device here, so a pause shorter
than that costs nothing: this says whether the machine pauses at all.
Source and helper: ``host_pause_max_ms``."""

from chipbench.layers.host_pause_max_ms import longest_pause_ms


def read(o):
    return longest_pause_ms(o.events, "train/step_dispatch", hull=True)
