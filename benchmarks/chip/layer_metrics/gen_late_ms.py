"""How late the load generator ran: 99th percentile of (sent - due) over the
window's requests, milliseconds.  A starved generator must not be read as a
fast server."""

import numpy as np


def read(sample):
    late = sample.get("lateness_ms")
    if late is None or not len(late):
        return None
    return float(np.percentile(late, 99))
