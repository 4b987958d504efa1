"""server_update_ms: device time the server spends per applied gradient.

The summed device time of the aggregator's stage and flush-apply
executables (``SlabAggregator._stage_impl`` and ``._flush_impl``) in the
traced window, over the gradients staged there: every applied gradient
is staged once."""
STAGE = "jit__stage_impl"
FLUSH = "jit__flush_impl"


def read(rec):
    s = rec.summary
    if s is None or not s.module_n.get(STAGE) or not s.module_n.get(FLUSH):
        return None
    return 1e3 * (s.module_s[STAGE] + s.module_s[FLUSH]) / s.module_n[STAGE]
