"""Live decode slots over all slots, averaged over the window's decode
steps."""


def read(run):
    steps = run.calls("decode")
    if not steps:
        return None
    slots = run.cfgfile["slots"]
    return sum(c.live for c in steps) / (slots * len(steps))
