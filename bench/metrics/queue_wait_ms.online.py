"""Mean wall-clock wait of a request from its due time to the start of the
dispatch that served it (the batcher adapter's records)."""


def read(run):
    online = run.window.online
    if not online or not online["dispatches"]:
        return None
    arrival = {r.rid: r.arrival for r in online["requests"]}
    waits = [d["start"] - arrival[rid] for d in online["dispatches"]
             for rid in d["rids"]]
    return 1e3 * sum(waits) / len(waits)
