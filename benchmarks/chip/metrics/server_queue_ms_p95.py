"""server_queue_ms_p95: how long gradients wait for the server's ingest.

From the harness's transport: for every gradient applied in the window,
the time from its ``send_gradient`` returning to ``recv_gradient``
handing it to the server's loop; the 95th percentile."""
import numpy as np


def read(rec):
    q = rec.out["queue_s"]
    return float(1e3 * np.percentile(q, 95)) if q else None
