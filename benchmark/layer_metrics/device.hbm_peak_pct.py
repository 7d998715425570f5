"""Peak device memory in use over the device's limit, the fullest chip
(the worker's ``/admin/memory``)."""
NAME, UNIT, LAYER, MOVES = "device.hbm_peak_pct", "%", "device", "out_tok_s"


def compute(run):
    best = None
    for d in (run.get("memory") or {}).get("devices", {}).values():
        peak, limit = d.get("peakBytesInUse"), d.get("bytesLimit")
        if peak and limit:
            best = max(best or 0.0, 100.0 * peak / limit)
    return best
