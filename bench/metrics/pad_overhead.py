"""``PortEngine``'s padding over the window: padded output elements over
the requests' own elements, less one (the engine's counters)."""


def read(record, trace, ctx):
    eng = record["engine"]
    if eng["payload_elems"] == 0:
        return None
    return eng["padded_elems"] / eng["payload_elems"] - 1.0
