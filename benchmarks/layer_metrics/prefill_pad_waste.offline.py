"""Share of the prefill programs' query positions that held no token: 1 -
prefill_query_tokens / prefill_token_slots, the scheduler's own sums of its
dispatch records over the window (programs prefill, packed, prefill_chunk,
rpa, mixed).  A count: it repeats exactly over runs of one seed.  None on a
tree whose scheduler hands out no such counters (before PR 25, or
LMRS_ANATOMY=0).

The reader also writes the window's per-program, per-key table (the
anatomy's ``programs`` block, which the sums are taken from) to standard
error, one line, so that a traced run's log says which programs and keys
the waste and any cold dispatch belong to."""

import json
import sys


def read(facts):
    programs = (facts.get("anatomy") or {}).get("programs")
    if programs:
        print("programs over the window: " + json.dumps(programs),
              file=sys.stderr, flush=True)
    c = facts["counters"]
    slots = c.get("prefill_token_slots")
    if not slots or "prefill_query_tokens" not in c:
        return None
    return 100.0 * (1.0 - c["prefill_query_tokens"] / slots)
