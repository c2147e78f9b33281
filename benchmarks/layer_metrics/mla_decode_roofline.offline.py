"""Latent decode kernel (``mla_paged_decode``) against the memory roofline:
the latent rows that the window's decode steps had to read (every generated
token j of a request reads its prompt + j cached rows, all layers, each
row once for scores and values: the family's ``decode_attn_bytes``) over
HBM bandwidth, divided by the kernel's device time in the trace.  Memory
bound.  A program without the kernel (the parent) has no such time: None."""
from _shared import kernel_seconds

KERNEL = ("mla_paged_decode",)


def read(facts):
    secs = kernel_seconds(facts, *KERNEL)
    if not secs or not facts.get("peaks"):
        return None
    m, fl = facts["model"], facts["flops"]
    tokens = 0.0
    for r in facts["window"]["requests"]:
        g = r["generated"]
        tokens += g * r["prompt"] + g * (g + 1) / 2.0
    floor_s = fl.decode_attn_bytes(m, tokens) / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / secs
