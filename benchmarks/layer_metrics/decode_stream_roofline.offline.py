"""The decode program against the memory roofline of everything a decode
step has to stream: the bytes the traced window's decode steps had to read
over HBM bandwidth, divided by the device seconds of the decode program
(``trace["programs"]``).  Bytes, from the scheduler's dispatch records of
the same window (``anatomy["programs"]["decode"]``): ``layer_passes`` x one
layer application's weight bytes (a looped stack reads its layers once a
pass) + the steps (``layer_passes`` / cache layers) x the head's bytes + the
K and V of the ``ctx_tokens`` each block found in its pages, read at every
one of the block's steps (the tokens a block itself adds are not counted:
a lower bound).  Memory bound.  The seconds are what the trace recorded: where
the profiler stops recording the device before the window ends (the
cell this reader was written for: 1.95 s of 28.6, PERF.md section 7) the steps
are all counted and the share reads high by the lost part.  A program whose records lack ``layer_passes``
(the parent of the PR that brought it) gives None."""

PROGRAM = ("jit_decode",)


def read(facts):
    tr, peaks = facts.get("trace"), facts.get("peaks")
    rec = (facts.get("anatomy") or {}).get("programs", {}).get("decode")
    fl = facts["flops"]
    if (not tr or not peaks or not rec or not rec.get("layer_passes")
            or not hasattr(fl, "layer_bytes")):
        return None
    secs = sum(s for name, s in tr["programs"].items()
               if any(name.startswith(p) for p in PROGRAM))
    if not secs:
        return None
    m = facts["model"]
    steps = rec["layer_passes"] / fl.cache_layers(m)
    nbytes = (rec["layer_passes"] * fl.layer_bytes(m)
              + steps * fl.head_bytes(m)
              + rec["ctx_tokens"] * steps / rec["dispatches"]
              * fl.kv_bytes_per_token(m))
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / secs
