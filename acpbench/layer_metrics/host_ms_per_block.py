"""Scheduler: host milliseconds of the engine loop per decode block inside
the window: the self time of every phase of `Engine.stats()["perf"]["phases"]`
but `fetch` (waiting on the device) and `park` (waiting for work), over the
decode blocks dispatched. Where the loop is serial this is what the chip
waits for."""

NOT_HOST = ("fetch", "park")


def read(run):
    edges = [run.stats.get(e, {}).get("perf", {}) for e in ("open", "close")]
    if not all("phases" in p and "blocks" in p for p in edges):
        return None
    blocks = edges[1]["blocks"] - edges[0]["blocks"]
    if not blocks:
        return None
    seconds = sum(row["s"] - edges[0]["phases"].get(name, {"s": 0.0})["s"]
                  for name, row in edges[1]["phases"].items() if name not in NOT_HOST)
    return seconds * 1e3 / blocks
