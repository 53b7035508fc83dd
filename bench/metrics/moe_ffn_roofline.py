"""Kernel: the held experts' FFN's share of its roofline.  The least time
the chip could take for the expert matmuls the window's steps needed
(the configuration family's ``moe_ffn_flops`` / ``moe_ffn_bytes``: the
rows routed to the held experts at their expected share, and each held
expert a row reaches read once an expert layer), the larger of
operations over the bf16 peak and bytes over the memory bandwidth, over
the summed device time of the loops that run them: the program's
``moe_experts`` scope marks its instructions (``repro.models.moe.scope``),
and each expert layer runs its tiles of held-expert rows in one such
``while``, whose event in the trace spans every operation inside it.
The operations inside carry the mark too and are not counted again.
A family without those counts, or a trace without such a loop (a
program without the scope), reads nothing."""
import re
import sys

SCOPE = "moe_experts"


def _opcode(name: str) -> str:
    head, eq, rest = name.partition(" = ")
    op = re.search(r"\s([a-z][\w.-]*)\(", rest) if eq else None
    return op.group(1) if op else ""


def experts_time(trace) -> float:
    return sum(t for name, t in trace.ops.items()
               if (SCOPE in name or SCOPE in trace.labels.get(name, ""))
               and _opcode(name) == "while")


def read(w):
    steps = [s for s in w.window_steps() if s.work is not None]
    flops_of = getattr(w.family, "moe_ffn_flops", None)
    bytes_of = getattr(w.family, "moe_ffn_bytes", None)
    if w.trace is None or not steps or flops_of is None or bytes_of is None:
        return None
    busy = experts_time(w.trace)
    if busy <= 0:
        return None
    flops = sum(flops_of(w.config, s.work) for s in steps)
    nbytes = sum(bytes_of(w.config, s.work) for s in steps)
    t_flops, t_bytes = flops / w.peak.bf16_flops, nbytes / w.peak.hbm_bw
    print(f"bench: moe_ffn_roofline is "
          f"{'memory' if t_bytes >= t_flops else 'compute'}-bound: {flops} "
          f"FLOP, {nbytes} bytes, experts {busy} s", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / busy
