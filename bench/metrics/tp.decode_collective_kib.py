"""Bytes that the collectives of one decode step carry per device, in
KiB: the program's ``tp.collectives`` counter (``decode.bytes``), which
the engine reads once from the compiled step's HLO and records at each
traced decode dispatch, averaged over the dispatches in the window.
None where the program records no such counter: one device, or a
program that has none."""


def read(run):
    engine = getattr(run.client, "engine", None)
    tracer = getattr(getattr(engine, "loop", None), "tracer", None)
    got = [e["args"]["decode.bytes"]
           for e in getattr(tracer, "events", None) or ()
           if e["ph"] == "C" and e["name"] == "tp.collectives"
           and "decode.bytes" in e.get("args", {})
           and run.lo <= e["ts"] * 1e-6 < run.hi]
    return sum(got) / len(got) / 1024 if got else None
