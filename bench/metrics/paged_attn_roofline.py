"""Kernel: the paged-attention kernel's share of its roofline.  The
least time the chip could take for the attention the window's steps
needed (the configuration family's ``paged_attn_flops`` /
``paged_attn_bytes``: each active lane's real K/V rows, queries and
outputs, every layer), the larger of operations over the bf16 peak and
bytes over the memory bandwidth, over the kernel's summed device time in
the trace.  Which bound applies is printed with the result.

The kernel's operations are those whose name or trace label holds one
of ``KERNEL``: the program gives its ``pallas_call`` no name, XLA names
the operation after the call around it, and its label keeps the op_name
``.../pallas_call``, the kernel function's name or the custom call's
target.  It is the step's only Pallas kernel."""
import sys

KERNEL = ("paged_attention", "pallas_call", "_paged_kernel",
          "tpu_custom_call")


def read(w):
    steps = [s for s in w.window_steps() if s.work is not None]
    if w.trace is None or not steps:
        return None
    kernel_s = w.trace.time_of(KERNEL)
    if kernel_s <= 0:
        return None
    flops = sum(w.family.paged_attn_flops(w.config, s.work) for s in steps)
    nbytes = sum(w.family.paged_attn_bytes(w.config, s.work) for s in steps)
    t_flops, t_bytes = flops / w.peak.bf16_flops, nbytes / w.peak.hbm_bw
    print(f"bench: paged_attn_roofline is "
          f"{'memory' if t_bytes >= t_flops else 'compute'}-bound: {flops} "
          f"FLOP, {nbytes} bytes, kernel {kernel_s} s", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / kernel_s
