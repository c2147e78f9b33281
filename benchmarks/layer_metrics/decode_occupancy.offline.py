"""Live rows per decode dispatch over slots: occupancy_sum /
decode_dispatches, windowed as bench._scheduler_window does."""


def read(facts):
    c = facts["counters"]
    if not c.get("decode_dispatches"):
        return None
    return 100.0 * c["occupancy_sum"] / c["decode_dispatches"]
