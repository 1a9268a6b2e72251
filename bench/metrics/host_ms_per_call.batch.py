"""Host time per ``search`` dispatch outside the device wait: over the
traced window, the ``session.pad``, ``session.dispatch``,
``session.fetch`` and ``session.record`` spans (the children of each
``engine.execute`` span of ``SearchSession._execute``) per
``engine.execute`` span. ``run.spans`` holds ``(name, seconds)`` of every
span the program's tracer recorded in the window."""

HOST = ("session.pad", "session.dispatch", "session.fetch", "session.record")


def read(run):
    calls = sum(1 for name, _ in run.spans if name == "engine.execute")
    host = [s for name, s in run.spans if name in HOST]
    if not calls or not host:
        return None
    return 1e3 * sum(host) / calls
