"""Share of the traced window in which the device was idle while the
serving loop waited for work (``serve.loop.wait``: nothing ready to
run) and the host was not compiling, averaged over the cell's devices
(``spans.idle_split``)."""
import spans


def read(run):
    return spans.read_share(run, "wait")
