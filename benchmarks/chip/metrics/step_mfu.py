"""step_mfu: model FLOP utilization of the whole training step.

Tokens of every gradient applied in the traced window (the harness's
publish records), times the forward and backward FLOPs a token needs
(``flops.train_flops_per_token``), over the window's length, the chips
and their bf16 peak.  Nothing where a block of the model has no count."""


def read(rec):
    if rec.summary is None or rec.peaks is None \
            or rec.flops_per_token is None:
        return None
    t0, t1 = rec.out["trace_mono"]
    grads = sum(k for t, k in rec.out["flush_times"] if t0 <= t < t1)
    tokens = grads * rec.traffic["batch"] * rec.traffic["seq"]
    peak = (t1 - t0) * rec.summary.devices * rec.peaks["bf16_flops_per_s"]
    return 100.0 * tokens * rec.flops_per_token / peak
