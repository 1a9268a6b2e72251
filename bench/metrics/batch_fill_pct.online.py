"""Query rows dispatched over the bucket rows they were padded to."""


def read(run):
    online = run.window.online
    if not online or not online["dispatches"]:
        return None
    dsp = online["dispatches"]
    return 100.0 * sum(d["rows"] for d in dsp) / sum(d["bucket"] for d in dsp)
