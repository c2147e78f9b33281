"""The decode program against the memory roofline of what its steps had to
read: the scheduler's own count over the window (``counters
["decode_hbm_bytes"]``, the sum of its decode records' ``hbm_bytes``, built
from the weight tree and KV pool the engine holds: each step's layer
applications, the head, every live row's positions in each kind of layer,
and the held experts the step touched) over HBM bandwidth, divided by the
device seconds of the programs whose name starts with ``jit_decode``
(``trace["programs"]``).  Memory bound, and a lower bound on the bytes
(positions, not pages).  A program without the counter (the parent of the
PR that brought it), or a run without a trace or a decode program: None."""

PROGRAM = "jit_decode"


def read(facts):
    tr, peaks = facts.get("trace"), facts.get("peaks")
    nbytes = (facts.get("counters") or {}).get("decode_hbm_bytes")
    if not tr or not peaks or not nbytes:
        return None
    secs = sum(s for name, s in tr["programs"].items()
               if name.startswith(PROGRAM))
    if not secs:
        return None
    return 100.0 * nbytes / (peaks["hbm_bytes_per_s"] * secs)
