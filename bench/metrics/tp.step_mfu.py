"""Model FLOP utilisation of the whole serving step of a cell served
over several chips, in percent: ``step_mfu``'s reading, by its own
reader (operations of the real tokens over window x chips x peak).
None on one chip."""
import harness

step_mfu = harness.load_reader("step_mfu")


def read(run):
    return step_mfu(run) if run.chips > 1 else None
