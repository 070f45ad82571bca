"""gemm_roofline.train: the U-Net's GEMM FLOPs in the window (convs,
timestep projections, attention's QK and PV, forward and backward,
counted from the shapes by bench/lib/macs.py) over the device time of
the trace's GEMM operations (dot and convolution fusions, the Pallas
matmul and attention kernels: bench/lib/trace.py ``classify``) times
the peak, in per cent.  At these shapes the GEMMs are compute-bound, so
the FLOP peak is their roofline.  Nothing where no GEMM time was read.
Moves train_img_per_s."""
from lib.readers import peak_flops, train_flops


def read(ctx, red):
    if not red or red["gemm_s"] <= 0 or not ctx.counters.get("images"):
        return None
    return 100.0 * train_flops(ctx) / (red["gemm_s"] * peak_flops(ctx))
