"""worker_grad_ms: device time of one gradient in the cluster worker.

The summed device time of the runtime's fused decode -> grad -> encode
executable in the traced window, over the number of times it ran there
(one run is one gradient).  The program names that executable after the
function it jits, ``ClusterRuntime._grad_slab``."""
MODULE = "jit__grad_slab"


def read(rec):
    s = rec.summary
    if s is None or not s.module_n.get(MODULE):
        return None
    return 1e3 * s.module_s[MODULE] / s.module_n[MODULE]
