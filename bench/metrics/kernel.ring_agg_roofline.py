"""Share of its roofline that the ``ring_agg`` Pallas kernel
(``kernels/weighted_agg`` ``ring_agg_2d``) reaches, per study.

The least time is that of the bytes the study's aggregation needs
(``counts.ring_agg_bytes``: each upload read once at its stored width, the
f32 model read and written once per chain the plan needs) at the chip's
fastest memory, VMEM, where XLA places the kernel's operands; reads and
writes are taken to overlap.  The time is the device time of the kernel's
events in the trace."""
import counts


def read(ctx):
    t = ctx.trace
    if not t or not t["kernel_s"].get("ring_agg_2d"):
        return None
    # every study of a run has the seed's event trace; the learning rate
    # changes only the training
    trace = next(s["answer"]["trace"] for s in ctx.studies if s["answer"])
    rd, wr = counts.ring_agg_bytes(ctx.cfg, [v for v, _ in trace],
                                   [r for _, r in trace],
                                   ctx.traffic["eval_every"])
    least = max(rd / ctx.peaks["vmem_read_bytes_per_s"],
                wr / ctx.peaks["vmem_write_bytes_per_s"])
    return 100.0 * least * t["studies"] / t["kernel_s"]["ring_agg_2d"]
