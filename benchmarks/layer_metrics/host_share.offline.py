"""Share of the scheduler's iteration wall spent outside the dispatch and
fetch segments (admit, plan, draft, finish, io and the residual): the
anatomy's own windowed totals, 100 x (wall_ms - dispatch - fetch) / wall_ms.
The host's clock, so it sits beside device_idle.offline, which is the
device's.  None where the anatomy is off or older than its programs table
(the metric came with the table, PR 25)."""


def read(facts):
    an = facts.get("anatomy") or {}
    segs = an.get("segments_ms") or {}
    wall = an.get("wall_ms") or 0.0
    if "programs" not in an or wall <= 0 or "dispatch" not in segs:
        return None
    return 100.0 * (wall - segs["dispatch"] - segs.get("fetch", 0.0)) / wall
