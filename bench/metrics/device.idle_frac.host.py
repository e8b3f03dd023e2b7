"""Share of the traced window in which the device was idle while the
host was inside any other program span (dispatch, readback, scheduling,
page scatters, table uploads, bookkeeping) and not compiling or
waiting, averaged over the cell's devices (``spans.idle_split``)."""
import spans


def read(run):
    return spans.read_share(run, "host")
