"""flush_roofline: the flush-apply executable's share of its roofline.

The least time a flush of ``k`` live rows can take is the bytes any
implementation must move (``flops.flush_bytes``: the live rows in the
staging dtype, the f32 master read and written, the published copy)
over the chip's HBM bandwidth; the share is that least time, summed over
the flushes in the traced window, over the device time of the
``SlabAggregator._flush_impl`` executable there.  Rows the kernel reads
beyond the live ones, and its f32 scratch, are not counted as needed."""
import flops

FLUSH = "jit__flush_impl"


def read(rec):
    s = rec.summary
    if s is None or not s.module_n.get(FLUSH) or rec.peaks is None:
        return None
    t0, t1 = rec.out["trace_mono"]
    ks = [k for t, k in rec.out["flush_times"] if t0 <= t < t1]
    if not ks:
        return None
    need = sum(flops.flush_bytes(rec.params, k, rec.traffic["slab_dtype"])
               for k in ks) / len(ks) * s.module_n[FLUSH]
    return 100.0 * need / rec.peaks["hbm_bytes_per_s"] / s.module_s[FLUSH]
