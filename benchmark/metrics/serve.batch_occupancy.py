"""Mean occupancy of the window's batches: the share of the batched
executable's slots that carry real requests (``serve_batch`` events)."""


def read(rec):
    occ = [e["occupancy"] for e in rec["program"].get("events", [])
           if e.get("event") == "serve_batch"]
    return 100.0 * sum(occ) / len(occ) if occ else None
