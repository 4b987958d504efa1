"""server_update_ms: device time the server spends per applied gradient.

The device time of a call of the aggregator's flush-apply executable
(``SlabAggregator._flush_impl``) in the traced window, over the mean
number of gradients that the flushes published in that window applied:
``flush_roofline``'s reading of the trace and the harness's publish
records.  The server stages a gradient by holding the worker's slab,
with no executable of its own, so the flush is the server's whole device
cost; with ``worker_grad_ms`` it sums to the device time of one
gradient.  Nothing when no flush falls in the window."""
import harness


def read(rec):
    got = harness.load_module("metrics", "flush_roofline").flushes(rec)
    if got is None:
        return None
    call_s, ks = got
    return 1e3 * call_s / (sum(ks) / len(ks))
